"""Streaming ingestion + incremental maintenance: the recompute-
equivalence harness.

The core oracle: after EVERY micro-batch, a standing query's cumulative
output must be row-for-row, order- and stats-equivalent to a cold full
recompute over the concatenated snapshot — across all 44 corpus
queries (donor-seeded mixed append schedules with empty batches and
duplicate-key floods) and a hypothesis-driven random schedule
(``ingest(A); ingest(B)`` ≡ ``ingest(A++B)`` ≡ cold, for filter / join
/ aggregate plans). Incremental ``llm_calls`` must equal the cold
full-recompute delta (PLOP's caching theorem over time), appends of
fully-cached keys must issue ZERO LLM calls, and the incremental
structures themselves must match the batch kernels bit-for-bit
(``StreamJoinBuild.probe`` vs ``hash_join_np``, ``groups`` vs
``dedup_representatives``) at zero syncs per ingest / one per probe.

The serving stress class pushes 100 micro-batches of 1–64 rows through
a shared ``FrontDoor`` on both serving disciplines, holding per-batch
drained↔continuous equivalence, the one-sync-per-round discipline and
the per-batch ``PIPELINE_SYNCS_SMALL_MAX`` budget.
"""
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# hypothesis is a dev-only dependency (requirements-dev.txt). Collection
# must never hard-fail without it: only the property tests skip.
try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ModuleNotFoundError:
    HAVE_HYPOTHESIS = False

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
from benchmarks.corpus import ALL_QUERIES  # noqa: E402
from benchmarks.pipeline_gate import PIPELINE_SYNCS_SMALL_MAX  # noqa: E402

from repro.configs import get_tiny  # noqa: E402
from repro.core import Q, optimize  # noqa: E402
from repro.core.builder import col  # noqa: E402
from repro.data import SCHEMAS  # noqa: E402
from repro.engine import Database, Executor, FrontDoor  # noqa: E402
from repro.kernels.hash_dedup.ops import dedup_representatives  # noqa: E402
from repro.kernels.hash_join.ref import hash_join_np  # noqa: E402
from repro.kernels.sync import HOST_SYNCS, SERVING_SITES  # noqa: E402
from repro.models import init_params  # noqa: E402
from repro.semantic import (  # noqa: E402
    ModelBackend,
    OracleBackend,
    SemanticRunner,
)
from repro.serving.engine import ServingEngine  # noqa: E402
from repro.sharding import ShardingPolicy  # noqa: E402
from repro.streaming import (  # noqa: E402
    StreamContext,
    StreamJoinBuild,
    StreamSession,
    append_rows,
    freeze_record,
)
from repro.training.data import HashTokenizer  # noqa: E402


def _frozen(recs):
    return [freeze_record(r) for r in recs]


def _cold_run(db, plan, out_cols=None):
    """Cold full recompute on the current snapshot: fresh runner, fresh
    caches, batch join kernels (no stream context)."""
    ex = Executor(db, SemanticRunner(OracleBackend(truths=db.truths)),
                  kernel_impl="ref")
    table, stats = ex.execute(plan)
    return db.materialize(table, out_cols), stats


# ---------------------------------------------------------------------------
# Unit: StreamJoinBuild vs the batch kernels, bit for bit
# ---------------------------------------------------------------------------

class _KeyTable:
    """Minimal Table stand-in: one device int32 key column."""

    def __init__(self, keys):
        self._k = jnp.asarray(np.asarray(keys, np.int32))

    def col(self, name):
        return self._k


class TestStreamJoinBuild:
    def test_probe_and_groups_match_batch_oracles(self):
        """Random append schedules (small min_cap forces growth
        rebuilds): after every extend, probe ≡ ``hash_join_np`` and
        groups ≡ ``dedup_representatives``, exactly."""
        rng = np.random.default_rng(0)
        for trial in range(4):
            allk = rng.integers(0, 20, size=int(rng.integers(0, 50))
                                ).astype(np.int32)
            b = StreamJoinBuild("t", "t.k", _KeyTable(allk), impl="ref",
                                min_cap=64)
            for _ in range(5):
                delta = rng.integers(0, 20, size=int(rng.integers(0, 40))
                                     ).astype(np.int32)
                allk = np.concatenate([allk, delta])
                b.extend(_KeyTable(allk))
                pk = rng.integers(0, 25, size=int(rng.integers(0, 60))
                                  ).astype(np.int32)
                gl, gr = (np.asarray(x) for x in
                          b.probe(jnp.asarray(pk)))
                el, er = hash_join_np(pk, allk)
                np.testing.assert_array_equal(gl, el)
                np.testing.assert_array_equal(gr, er)
                _, reps, inverse = dedup_representatives(
                    allk.reshape(-1, 1), impl="ref")
                g = b.groups()
                assert g.num_groups == len(reps) == b.distinct
                np.testing.assert_array_equal(g.reps,
                                              reps.astype(np.int32))
                np.testing.assert_array_equal(
                    g.counts, np.bincount(inverse, minlength=len(reps)
                                          ).astype(np.int32))
                np.testing.assert_array_equal(g.group_ids,
                                              inverse.astype(np.int32))
            assert b.rebuilds >= 1, "growth path never exercised"

    def test_ingest_is_sync_free_probe_costs_one(self):
        rng = np.random.default_rng(1)
        allk = rng.integers(0, 9, size=40).astype(np.int32)
        b = StreamJoinBuild("t", "t.k", _KeyTable(allk), impl="ref",
                            min_cap=64)
        delta = rng.integers(0, 9, size=30).astype(np.int32)
        allk = np.concatenate([allk, delta])
        before = HOST_SYNCS.syncs
        b.extend(_KeyTable(allk))
        assert HOST_SYNCS.syncs == before, "ingest must cost 0 syncs"
        snap0 = HOST_SYNCS.snapshot()["by_site"].get("stream_probe", 0)
        before = HOST_SYNCS.syncs
        b.probe(jnp.asarray(rng.integers(0, 12, size=25), jnp.int32))
        assert HOST_SYNCS.syncs == before + 1
        assert HOST_SYNCS.snapshot()["by_site"]["stream_probe"] \
            == snap0 + 1

    def test_empty_paths(self):
        b = StreamJoinBuild("t", "t.k", _KeyTable([]), impl="ref",
                            min_cap=64)
        out = b.probe(jnp.asarray(np.asarray([1, 2], np.int32)))
        assert all(np.asarray(x).size == 0 for x in out)
        assert b.groups().num_groups == 0
        out = b.probe(jnp.zeros(0, jnp.int32))
        assert all(np.asarray(x).size == 0 for x in out)

    def test_host_impl_defers_to_batch_join(self):
        b = StreamJoinBuild("t", "t.k", _KeyTable([1, 2]), impl="ref")
        assert b.probe(jnp.asarray(np.asarray([1], np.int32)),
                       impl="host") is None


# ---------------------------------------------------------------------------
# Append contract
# ---------------------------------------------------------------------------

def _tiny_db(events):
    db = Database()
    db.add_table("events", events)
    return db


class TestAppendRows:
    def test_snapshot_matches_cold_add_table(self):
        recs = [{"eid": i, "k": i % 3, "v": float(i)} for i in range(7)]
        extra = [{"eid": 7, "k": 9, "v": 1.5},
                 {"eid": 8, "k": 0, "v": float("nan")}]
        cold = _tiny_db(list(recs) + extra)
        db = _tiny_db(list(recs))  # copy: append extends the payload
        db.tables["events"].num_valid  # cache, as an executor would
        before = HOST_SYNCS.syncs
        t = append_rows(db, "events", extra)
        assert HOST_SYNCS.syncs == before, "append must cost 0 syncs"
        assert t.num_valid == 9  # extended arithmetically, no re-fetch
        for q in cold.tables["events"].columns:
            np.testing.assert_array_equal(
                np.asarray(t.col(q)),
                np.asarray(cold.tables["events"].col(q)), err_msg=q)
        assert db.payloads["events"] == cold.payloads["events"]

    def test_empty_batch_is_noop(self):
        db = _tiny_db([{"eid": 0, "k": 1}])
        t0 = db.tables["events"]
        assert append_rows(db, "events", []) is t0

    def test_missing_column_fails_loud(self):
        db = _tiny_db([{"eid": 0, "k": 1}])
        with pytest.raises(KeyError):
            append_rows(db, "events", [{"eid": 1}])

    def test_none_becomes_nan_for_float_columns(self):
        db = _tiny_db([{"eid": 0, "v": 1.0}])
        t = append_rows(db, "events", [{"eid": 1, "v": None}])
        assert np.isnan(np.asarray(t.col("events.v"))[1])


# ---------------------------------------------------------------------------
# The 44-query corpus replay: incremental ≡ cold after every micro-batch
# ---------------------------------------------------------------------------

_SCHEMAS = sorted({s.schema for s in ALL_QUERIES})


def _append_schedule(db, donor, rng):
    """Mixed micro-batch schedule from a donor database (same generator,
    different seed — so appended rows carry coherent latent truth fields
    and text payloads): one slice per table, an empty batch, and a
    duplicate-key flood of a single donor row."""
    tables = sorted(db.tables)
    batches = []
    for t in tables:
        pool = donor.payloads[t]
        k = int(rng.integers(1, max(2, min(40, len(pool)))))
        batches.append((t, pool[:k]))
    flood_t = tables[int(rng.integers(0, len(tables)))]
    batches.append((flood_t, []))  # empty batch
    flood_row = donor.payloads[flood_t][0]
    batches.append((flood_t, [flood_row] * 64))  # duplicate-key flood
    return batches


@pytest.mark.parametrize("schema", _SCHEMAS)
def test_corpus_replay_incremental_equals_cold(schema):
    """After every micro-batch, every corpus query's standing output is
    row-for-row and ORDER-equivalent to a cold recompute on the
    concatenated snapshot, per-batch incremental llm_calls equal the
    cold delta, and cumulative incremental llm_calls equal the cold
    total (the caching theorem over time)."""
    specs = [s for s in ALL_QUERIES if s.schema == schema]
    db = SCHEMAS[schema](seed=0, scale=0.1)
    donor = SCHEMAS[schema](seed=1, scale=0.1)
    sess = StreamSession(db, OracleBackend(truths=db.truths),
                         kernel_impl="ref")
    plans, prev_cold_llm = {}, {}
    for spec in specs:
        plans[spec.qid] = optimize(spec.build(), db.catalog(),
                                   strategy="cost").plan
        sq = sess.register(spec.qid, plans[spec.qid],
                           out_cols=spec.out_cols)
        prev_cold_llm[spec.qid] = sq.last_stats.llm_calls

    stream_joins = 0
    rng = np.random.default_rng(7)
    for bi, (tname, records) in enumerate(_append_schedule(db, donor,
                                                           rng)):
        deltas = sess.ingest(tname, records)
        for spec in specs:
            d = deltas[spec.qid]
            cold, cold_stats = _cold_run(db, plans[spec.qid],
                                         list(spec.out_cols))
            assert _frozen(d.output) == _frozen(cold), \
                f"{spec.qid}: batch {bi} diverged from cold recompute"
            assert d.stats.llm_calls == \
                cold_stats.llm_calls - prev_cold_llm[spec.qid], \
                f"{spec.qid}: batch {bi} llm_calls != cold delta"
            assert sess.queries[spec.qid].total_llm_calls == \
                cold_stats.llm_calls, \
                f"{spec.qid}: cumulative llm_calls != cold total"
            prev_cold_llm[spec.qid] = cold_stats.llm_calls
            stream_joins += d.stats.join_physical.get("stream", 0)
    assert stream_joins > 0, \
        "no query ever exercised the incremental stream join"


# ---------------------------------------------------------------------------
# Incremental cache accounting regressions
# ---------------------------------------------------------------------------

_PHI_CATS = "SEMANTIC: is category {cats.text} perishable?"


def _cats_events_db(n_events=120, n_cats=12, seed=0):
    db = Database()
    cats = [{"cat_id": i, "text": f"category {i}"}
            for i in range(n_cats)]
    rng = np.random.default_rng(seed)
    events = [{"event_id": j, "cat_id": int(rng.integers(0, n_cats))}
              for j in range(n_events)]
    db.add_table("cats", cats, text_columns={"text"})
    db.add_table("events", events)
    db.truths = {_PHI_CATS: lambda ctx: ctx["cats"]["cat_id"] % 3 == 0}
    return db


def _cats_events_plan():
    return (Q.scan("events")
            .join(Q.scan("cats"), "events.cat_id", "cats.cat_id")
            .sem_filter(_PHI_CATS)
            .build())


class TestIncrementalCacheAccounting:
    def test_fully_cached_append_issues_zero_llm_calls(self):
        """Appending rows whose semantic keys are all already cached:
        llm_calls == 0 and cache_hits == the row multiplicities (every
        join-output row probes, none dispatches)."""
        db = _cats_events_db()
        sess = StreamSession(db, OracleBackend(truths=db.truths),
                             kernel_impl="ref")
        sq = sess.register("q", _cats_events_plan(),
                           out_cols=["events.event_id", "cats.cat_id"])
        assert sq.last_stats.llm_calls == 12  # one per distinct cat
        rng = np.random.default_rng(3)
        n0 = 120
        for ne in (1, 17, 64):
            recs = [{"event_id": n0 + j,
                     "cat_id": int(rng.integers(0, 12))}
                    for j in range(ne)]
            n0 += ne
            d = sess.ingest("events", recs)["q"]
            assert d.stats.llm_calls == 0
            # every row of the refreshed join output re-probes the warm
            # cache: hits == total row multiplicities at this snapshot
            assert d.stats.cache_hits == n0
            assert d.stats.join_physical == {"stream": 1}
            assert not d.removed

    def test_duplicate_flood_one_key_10k_rows(self):
        """One key × 10k appended rows: zero LLM calls, 10k extra
        row-weighted hits, output grows by exactly the matching rows."""
        db = _cats_events_db()
        sess = StreamSession(db, OracleBackend(truths=db.truths),
                             kernel_impl="ref")
        sq = sess.register("q", _cats_events_plan(),
                           out_cols=["events.event_id", "cats.cat_id"])
        rows0 = len(sq._prev)
        flood = [{"event_id": 120 + j, "cat_id": 3}
                 for j in range(10_000)]
        d = sess.ingest("events", flood)["q"]
        assert d.stats.llm_calls == 0
        assert d.stats.cache_hits == 120 + 10_000
        # cat 3 passes the truth (3 % 3 == 0): all 10k rows surface
        assert len(d.added) == 10_000 and not d.removed
        cold, cold_stats = _cold_run(
            db, _cats_events_plan(),
            ["events.event_id", "cats.cat_id"])
        assert len(cold) == rows0 + 10_000
        assert _frozen(d.output) == _frozen(cold)
        assert cold_stats.llm_calls == 12  # cold pays only distinct keys


# ---------------------------------------------------------------------------
# Hypothesis: metamorphic ingest equivalence (CI property job)
# ---------------------------------------------------------------------------

_PHI_TAG = "SEMANTIC: does the tag {facts.tag} sound positive?"
_PHI_DIM = "SEMANTIC: is dimension {dims.text} even-numbered?"

_TRUTHS = {
    _PHI_TAG: lambda ctx: bool(ctx["facts"]["_flag"]),
    _PHI_DIM: lambda ctx: ctx["dims"]["id"] % 2 == 0,
}

_METAMORPHIC_PLANS = {
    "filter": lambda: (Q.scan("facts")
                       .where(col("facts.fk") <= 3)
                       .sem_filter(_PHI_TAG).build()),
    "join": lambda: (Q.scan("facts")
                     .join(Q.scan("dims"), "facts.fk", "dims.id")
                     .sem_filter(_PHI_DIM).build()),
    "aggregate": lambda: (Q.scan("facts")
                          .sem_filter(_PHI_TAG)
                          .group_by(["facts.fk"],
                                    [("sum", "facts.val", "s"),
                                     ("count", "*", "c")]).build()),
}


def _metamorphic_db(facts):
    db = Database()
    db.add_table("dims", [{"id": i, "text": f"dim {i}"}
                          for i in range(8)],
                 text_columns={"text"})
    db.add_table("facts", list(facts), text_columns={"tag"})
    db.truths = dict(_TRUTHS)
    return db


def _fact(eid, fk, val, tag, flag):
    return {"eid": eid, "fk": fk, "val": val, "tag": tag, "_flag": flag}


if not HAVE_HYPOTHESIS:

    def test_metamorphic_ingest_requires_hypothesis():
        pytest.importorskip("hypothesis")

else:
    _fact_st = st.tuples(
        st.integers(0, 9),  # fk: small range → duplicate floods
        st.sampled_from([0.5, -2.0, 7.25, float("nan")]),
        st.sampled_from(["good", "bad", "meh"]),
        st.booleans())

    class TestMetamorphicIngest:
        @settings(max_examples=10, deadline=None)
        @given(st.lists(_fact_st, min_size=1, max_size=20),
               st.lists(_fact_st, max_size=30), st.data())
        def test_split_ingest_equals_whole_equals_cold(self, base_t,
                                                       stream_t, data):
            """``ingest(A); ingest(B)`` ≡ ``ingest(A++B)`` ≡ cold, for
            filter / join / aggregate plans: identical rows, order and
            cumulative llm_calls on every path."""
            split = data.draw(st.integers(0, len(stream_t)))
            base = [_fact(i, *t) for i, t in enumerate(base_t)]
            stream = [_fact(len(base) + i, *t)
                      for i, t in enumerate(stream_t)]
            a, bb = stream[:split], stream[split:]

            outputs, llm = {}, {}
            for path in ("split", "whole"):
                db = _metamorphic_db(base)
                sess = StreamSession(db, OracleBackend(truths=db.truths),
                                     kernel_impl="ref")
                for name, mk in _METAMORPHIC_PLANS.items():
                    sess.register(name, mk())
                for chunk in ((a, bb) if path == "split" else (stream,)):
                    sess.ingest("facts", chunk)
                outputs[path] = {
                    q: _frozen(sq._prev)
                    for q, sq in sess.queries.items()}
                llm[path] = {q: sq.total_llm_calls
                             for q, sq in sess.queries.items()}

            cold_db = _metamorphic_db(base + stream)
            for name, mk in _METAMORPHIC_PLANS.items():
                cold, cold_stats = _cold_run(cold_db, mk())
                assert outputs["split"][name] == \
                    outputs["whole"][name] == _frozen(cold), name
                assert llm["split"][name] == llm["whole"][name] \
                    == cold_stats.llm_calls, name


# ---------------------------------------------------------------------------
# Serving-tier stress: 100 micro-batches through a shared FrontDoor
# ---------------------------------------------------------------------------

_CFG = get_tiny("stablelm-3b").replace(vocab_size=512)
_PARAMS = None


def _make_engine():
    global _PARAMS
    if _PARAMS is None:
        _PARAMS = init_params(_CFG, jax.random.PRNGKey(0))
    return ServingEngine(_CFG, _PARAMS, ShardingPolicy.single(),
                         tokenizer=HashTokenizer(_CFG.vocab_size),
                         batch_size=8, max_seq=48, max_new_tokens=2)


def _stress_batches(n_batches=100, seed=11):
    """100 micro-batches of 1–64 event rows; every 9th batch also adds
    a fresh cat first, so new semantic keys keep trickling through the
    row-weighted serving tickets."""
    rng = np.random.default_rng(seed)
    batches, n_events, n_cats = [], 64, 12
    for i in range(n_batches):
        cats = []
        if i % 9 == 8:
            cats = [{"cat_id": n_cats, "text": f"category {n_cats}"}]
            n_cats += 1
        ne = int(rng.integers(1, 65))
        events = [{"event_id": n_events + j,
                   "cat_id": int(rng.integers(0, n_cats))}
                  for j in range(ne)]
        n_events += ne
        batches.append((cats, events))
    return batches


def _stress_run(continuous, batches):
    eng = _make_engine()
    backend = ModelBackend.from_engine(eng, continuous=continuous)
    runner = SemanticRunner(backend)
    db = _cats_events_db(n_events=64, n_cats=12, seed=5)
    plan = _cats_events_plan()
    door = FrontDoor(db, runner, n_lanes=4, kernel_impl="ref")
    ctx = StreamContext(db, kernel_impl="ref")
    ctx.register_plan(plan)
    for lane in door.lanes:
        lane.stream = ctx
    per_batch = []
    door.execute(plan)  # prime caches on the base snapshot
    for cats, events in batches:
        if cats:
            ctx.append("cats", cats)
        ctx.append("events", events)
        table, stats = door.execute(plan)
        per_batch.append((table.num_valid, stats))
    return per_batch, eng


class TestServingStress:
    def test_100_micro_batches_shared_front_door(self):
        batches = _stress_batches()
        HOST_SYNCS.reset()
        cont, eng_c = _stress_run(True, batches)
        drained, _ = _stress_run(False, batches)
        stream_served = 0
        for bi, ((rows_c, sc), (rows_d, sd)) in enumerate(
                zip(cont, drained)):
            # drained ↔ continuous equivalence, per micro-batch
            assert rows_c == rows_d, f"batch {bi}: rows diverge"
            for f in ("llm_calls", "cache_hits", "null_skipped",
                      "probe_rows", "pipeline_syncs"):
                assert getattr(sc, f) == getattr(sd, f), (bi, f)
            # per-operator sync budget holds at micro-batch sizes
            assert sc.pipeline_syncs <= PIPELINE_SYNCS_SMALL_MAX, bi
            stream_served += sc.join_physical.get("stream", 0)
        # the incremental build served (nearly) every join; batch
        # rebuild only on capacity growth
        assert stream_served >= 90
        # one-sync-per-round: the continuous run's serving fetches are
        # at most its scheduling rounds (linear in rounds, not in rows)
        cont_serving = sum(s.serving_syncs for _, s in cont)
        assert cont_serving <= eng_c.stats.rounds
        new_key_batches = sum(1 for _, s in cont if s.llm_calls > 0)
        assert new_key_batches >= 11  # every injected cat dispatched
