"""The span recorder (``repro.spans``): off by default at no cost, the
nesting of parents and query ids, self-time arithmetic, and the spans
one e-commerce query leaves in every layer, from the planner down to
the serving loop's fetch."""
import sys
from collections import Counter
from pathlib import Path

import jax
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
from benchmarks.corpus import ALL_QUERIES  # noqa: E402

from repro import spans  # noqa: E402
from repro.configs import get_tiny  # noqa: E402
from repro.core import optimize  # noqa: E402
from repro.data import SCHEMAS  # noqa: E402
from repro.engine import FrontDoor  # noqa: E402
from repro.models import init_params  # noqa: E402
from repro.semantic import ModelBackend, SemanticRunner  # noqa: E402
from repro.serving.engine import ServingEngine  # noqa: E402
from repro.sharding import ShardingPolicy  # noqa: E402
from repro.training.data import HashTokenizer  # noqa: E402


@pytest.fixture
def recording():
    """Recording on for one test; the recorder is left off and empty."""
    spans.take()
    spans.enable()
    try:
        yield
    finally:
        spans.disable()
        spans.take()


def test_off_returns_the_shared_null_and_records_nothing():
    spans.take()
    first = spans.span("executor.query")
    assert first is spans.span("serving.fetch", None)
    assert first is spans.span("executor", "Join")
    with first:
        with spans.span("semantic.probe"):
            pass
    assert spans.take() == []


def test_nesting_gives_parents_and_query_ids(recording):
    with spans.span("planner.optimize"):
        pass
    with spans.span(spans.QUERY):
        with spans.span("executor", "Scan"):
            pass
        with spans.span("semantic", "SemanticFilter"):
            with spans.span("semantic.probe"):
                with spans.span("backend.submit"):
                    pass
    got = {s.name: s for s in spans.take()}
    assert spans.take() == []
    plan, q = got["planner.optimize"], got[spans.QUERY]
    assert (plan.parent_id, plan.query_id) == (None, None)
    assert (q.parent_id, q.query_id) == (None, q.span_id)
    sf = got["semantic.SemanticFilter"]
    assert got["executor.Scan"].parent_id == q.span_id
    assert sf.parent_id == q.span_id
    assert got["semantic.probe"].parent_id == sf.span_id
    assert got["backend.submit"].parent_id == got["semantic.probe"].span_id
    assert all(got[n].query_id == q.span_id for n in (
        "executor.Scan", "semantic.SemanticFilter", "semantic.probe",
        "backend.submit"))
    assert len({s.span_id for s in got.values()}) == len(got)
    # spans are handed over in the order they ended
    assert list(got) == ["planner.optimize", "executor.Scan",
                         "backend.submit", "semantic.probe",
                         "semantic.SemanticFilter", spans.QUERY]


def test_second_query_has_its_own_id(recording):
    for _ in range(2):
        with spans.span(spans.QUERY):
            with spans.span("executor", "Filter"):
                pass
    filt1, q1, filt2, q2 = spans.take()
    assert q1.span_id != q2.span_id
    assert (filt1.query_id, filt2.query_id) == (q1.span_id, q2.span_id)


def test_a_span_records_even_when_its_body_raises(recording):
    with pytest.raises(ValueError):
        with spans.span(spans.QUERY):
            raise ValueError("boom")
    with spans.span("planner.optimize"):
        pass
    failed, after = spans.take()
    assert failed.name == spans.QUERY
    # the failed span left the stack: the next one is a root
    assert after.parent_id is None and after.query_id is None


def test_self_time_subtracts_the_children():
    S = spans.Span
    recs = [
        S("executor.query", 0, 100, 1, None, 1),
        S("semantic.SemanticFilter", 10, 90, 2, 1, 1),
        S("semantic.probe", 20, 60, 3, 2, 1),
        S("backend.submit", 25, 35, 4, 3, 1),
        S("backend.collect", 40, 58, 5, 3, 1),
        S("semantic.bind", 70, 80, 6, 2, 1),
        S("planner.optimize", 200, 230, 7, None, None),
    ]
    got = spans.self_ns(recs)
    assert got == {1: 100 - 80, 2: 80 - 40 - 10, 3: 40 - 10 - 18,
                   4: 10, 5: 18, 6: 10, 7: 30}
    # self times of a tree add up to its root's duration
    assert sum(got[i] for i in range(1, 7)) == 100
    # a child outside the given spans is not subtracted
    assert spans.self_ns(recs[2:3]) == {3: 40}


def _q6():
    return next(q for q in ALL_QUERIES if q.qid == "q6"
                and q.schema == "ecommerce")


def test_one_query_spans_every_layer(recording):
    cfg = get_tiny("stablelm-3b").replace(vocab_size=512)
    eng = ServingEngine(cfg, init_params(cfg, jax.random.PRNGKey(0)),
                        ShardingPolicy.single(),
                        tokenizer=HashTokenizer(cfg.vocab_size),
                        batch_size=4, max_seq=48, max_new_tokens=2)
    db = SCHEMAS["ecommerce"](seed=0, scale=0.05)
    door = FrontDoor(db, SemanticRunner(ModelBackend.from_engine(eng)),
                     n_lanes=1)
    spans.take()
    plan = optimize(_q6().build(), db.catalog(), strategy="cost").plan
    _, stats = door.execute(plan)
    recs = spans.take()
    by_id = {s.span_id: s for s in recs}
    names = Counter(s.name for s in recs)

    def parent(s):
        return by_id[s.parent_id].name if s.parent_id else None

    def ancestors(s):
        out = []
        while s.parent_id:
            s = by_id[s.parent_id]
            out.append(s.name)
        return out

    assert stats.llm_calls > 0
    (query,) = [s for s in recs if s.name == spans.QUERY]
    assert {"planner.optimize", "executor.Scan", "executor.Join",
            "executor.Filter", "executor.Project",
            "semantic.SemanticFilter", "semantic.dedup",
            "semantic.render", "semantic.probe", "semantic.bind",
            "backend.submit", "backend.collect", "serving.encode",
            "serving.admit", "serving.round",
            "serving.fetch"} <= set(names)
    for s in recs:
        if s.name == "planner.optimize":
            assert (s.parent_id, s.query_id) == (None, None)
            continue
        assert s.query_id == query.span_id, s
        if s.name.startswith("executor.") and s is not query:
            assert parent(s) == spans.QUERY, s
        elif s.name == "semantic.SemanticFilter":
            assert parent(s) == spans.QUERY
        elif s.name.startswith("semantic."):
            assert "semantic.SemanticFilter" in ancestors(s), s
        elif s.name.startswith("backend."):
            assert parent(s) == "semantic.probe", s
        elif s.name == "serving.encode":
            assert parent(s) == "backend.submit"
        elif s.name == "serving.admit":
            assert parent(s) in ("backend.submit", "backend.collect")
        elif s.name == "serving.round":
            assert parent(s) == "backend.collect"
        elif s.name == "serving.fetch":
            assert parent(s) == "serving.round"
        assert query.start_ns <= s.start_ns <= s.end_ns <= query.end_ns
    # one fetch a scheduling round (with or without a decode step in
    # it), one admission span a prefill launch
    assert names["serving.fetch"] == names["serving.round"] \
        == eng.stats.rounds >= eng.stats.decode_steps
    assert names["serving.admit"] == eng.stats.batches
    # every launch computes max_seq positions a row, padding included
    assert eng.stats.prefill_token_slots == (eng.stats.prefill_rows
                                             * eng.max_seq)
    assert 0 < eng.stats.prefill_tokens < eng.stats.prefill_token_slots
