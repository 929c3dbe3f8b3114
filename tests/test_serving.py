"""Serving tier tests: continuous slot scheduler vs drained baseline.

Covers the scheduler's admission/recycling invariants (a slot freed
mid-decode is reused while its neighbours keep decoding, FIFO fairness
under equal weights, weighted fairness under skew), drained↔continuous
answer equivalence (including shuffled arrival order and partial final
chunks), the serving sync-site accounting, and drained↔continuous
stats equivalence over the full 44-query corpus behind the
shared-cache multi-query front door.
"""
import random
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
from benchmarks.corpus import ALL_QUERIES  # noqa: E402

from repro.configs import get_tiny  # noqa: E402
from repro.core import optimize  # noqa: E402
from repro.data import SCHEMAS  # noqa: E402
from repro.engine import FrontDoor, result_f1  # noqa: E402
from repro.kernels.sync import HOST_SYNCS, SERVING_SITES  # noqa: E402
from repro.models import forward, init_params, prefill  # noqa: E402
from repro.semantic import ModelBackend, SemanticRunner  # noqa: E402
from repro.serving.engine import ServingEngine, ServingStats  # noqa: E402
from repro.sharding import ShardingPolicy  # noqa: E402
from repro.training.data import HashTokenizer  # noqa: E402

_CFG = get_tiny("stablelm-3b").replace(vocab_size=512)
_PARAMS = None


def _make_engine(batch_size=4, max_seq=24, max_new=2):
    global _PARAMS
    if _PARAMS is None:
        _PARAMS = init_params(_CFG, jax.random.PRNGKey(0))
    return ServingEngine(_CFG, _PARAMS, ShardingPolicy.single(),
                         tokenizer=HashTokenizer(_CFG.vocab_size),
                         batch_size=batch_size, max_seq=max_seq,
                         max_new_tokens=max_new)


@pytest.fixture(scope="module")
def engine():
    return _make_engine()


class TestServingEngine:
    def test_answers_all_prompts(self, engine):
        engine.stats = ServingStats()
        prompts = [f"is item {i} acceptable?" for i in range(10)]
        out = engine.answer(prompts)
        assert len(out) == 10
        assert all(isinstance(a, str) and a for a in out)
        assert engine.stats.batches == 3  # bucketed admission: 4+4+2
        # bucketed admission never prefills a dead slot
        assert engine.stats.prefill_rows == engine.stats.live_prefill_rows

    def test_deterministic(self, engine):
        p = ["does this review sound positive?"]
        a1 = engine.answer(p)
        a2 = engine.answer(p)
        assert a1 == a2

    def test_model_backend_parses(self, engine):
        backend = ModelBackend(engine.answer)
        vals = backend.evaluate_batch(
            ["prompt a", "prompt b"],
            [{"__dtype__": "bool"}, {"__dtype__": "bool"}])
        assert all(isinstance(v, bool) for v in vals)
        assert backend.calls == 2

    def test_decode_stats_accumulate(self, engine):
        before = engine.stats.decode_steps
        engine.answer(["one more prompt"])
        assert engine.stats.decode_steps > before

    def test_serving_programs_keep_their_trace_names(self, engine):
        """A profiler trace names each program by its HLO module; the
        benchmark's kernels readers look for these two names."""
        s = engine.scheduler
        state = (engine.params, s._cache, s._cur, s._pos, s._live, s._rem,
                 s._head)
        adm = np.zeros((2, engine.max_seq + 2), dtype=np.int32)
        for fn, args, name in (
                (engine._prefill_insert, state + (adm,),
                 "jit__prefill_insert"),
                (engine._decode_round, state, "jit__decode_round")):
            first = fn.lower(*args).as_text().split("\n", 1)[0]
            assert first.startswith(f"module @{name} "), first

    def test_prefill_token_slots_count_padding(self):
        """Both disciplines count every prefilled position, padding
        included, beside the real prompt tokens."""
        eng = _make_engine()
        eng.stats = ServingStats()
        prompts = [f"short prompt {i}" for i in range(5)]
        eng.answer(prompts)  # buckets 4 + 1
        assert eng.stats.prefill_token_slots == 5 * eng.max_seq
        eng.stats = ServingStats()
        eng.answer_drained(prompts)  # two full-width chunks
        assert eng.stats.prefill_token_slots == 2 * 4 * eng.max_seq
        assert eng.stats.prefill_tokens == sum(
            eng.encode_row(p)[1] for p in prompts)
        assert eng.stats.snapshot()["prefill_token_slots"] == \
            eng.stats.prefill_token_slots


class TestSlotScheduler:
    def test_slot_freed_mid_decode_is_reused(self):
        """A finished sequence frees its slot while neighbours are
        still decoding, and the next submit recycles it immediately."""
        eng = _make_engine(max_new=3)
        sched = eng.scheduler
        ta = eng.submit(["first long-running prompt"])
        assert sched.live_slots() == [0]
        eng.poll()  # prefill's token reaches the host: no decode step
        assert eng.stats.decode_steps == 0
        eng.poll()  # request a now one round from its token budget
        tb = eng.submit([f"second wave prompt {i}" for i in range(3)])
        assert sched.live_slots() == [0, 1, 2, 3]
        eng.poll()  # a exhausts its budget; b's are mid-decode
        assert eng.done(ta) and not eng.done(tb)
        assert sched.free_slots() == [0]  # freed mid-decode
        assert sched.live_slots() == [1, 2, 3]
        tc = eng.submit(["third prompt lands in the recycled slot"])
        assert sched.live_slots() == [0, 1, 2, 3]  # slot 0 reused
        assert sched._slot_req[0].rid == tc.rids[0]
        eng.drain()
        for t in (ta, tb, tc):
            assert eng.done(t)
            assert all(a for a in eng.answers(t))

    def test_fifo_admission_under_equal_weights(self):
        """With equal weights the admission queue is FIFO: requests
        reach slots in arrival order, earlier waves strictly first."""
        eng = _make_engine()
        busy = eng.submit([f"busy slot filler {i}" for i in range(4)])
        rest = eng.submit([f"queued prompt {i}" for i in range(6)])
        reqs = [eng.scheduler._reqs[r] for r in rest.rids]
        eng.drain()
        admits = [r.t_admit for r in reqs]
        assert admits == sorted(admits)  # arrival order preserved
        # first freed wave (4 slots) strictly precedes the last two
        assert max(admits[:4]) < min(admits[4:])
        eng.answers(busy), eng.answers(rest)

    def test_weighted_admission_under_skew(self):
        """A late heavy request (standing for many rows) is admitted
        ahead of earlier singletons: key = arrival_seq / weight."""
        eng = _make_engine()
        busy = eng.submit([f"busy slot filler {i}" for i in range(4)])
        light = eng.submit([f"light singleton {i}" for i in range(5)],
                           weights=[1.0] * 5)
        heavy = eng.submit(["heavy many-row representative"],
                           weights=[1000.0])
        lr = [eng.scheduler._reqs[r] for r in light.rids]
        hr = eng.scheduler._reqs[heavy.rids[0]]
        eng.drain()
        assert all(hr.t_admit <= r.t_admit for r in lr)
        assert any(hr.t_admit < r.t_admit for r in lr)
        eng.answers(busy), eng.answers(light), eng.answers(heavy)

    def test_bucketed_admission_shapes(self):
        """Backlogs admit via power-of-two buckets (largest first), so
        a partial chunk never prefills dead slots."""
        eng = _make_engine()
        eng.stats = ServingStats()
        eng.answer([f"bucket shape probe {i}" for i in range(7)])
        assert eng.stats.prefill_rows == eng.stats.live_prefill_rows == 7
        assert eng.stats.batches == 3  # widths 4 + 2 + 1
        assert eng.stats.prefill_occupancy == 1.0


class TestDrainedContinuousEquivalence:
    def test_answers_match_incl_partial_final_chunk(self, engine):
        prompts = [f"partial chunk prompt {i}" for i in range(7)]
        assert engine.answer(prompts) == engine.answer_drained(prompts)

    def test_shuffled_arrival_order(self, engine):
        prompts = [f"shuffled arrival prompt {i}" for i in range(13)]
        base = engine.answer_drained(prompts)
        perm = random.Random(7).sample(range(13), 13)
        shuf = engine.answer([prompts[i] for i in perm])
        assert [shuf[perm.index(i)] for i in range(13)] == base

    def test_interleaved_tickets(self, engine):
        a = [f"ticket a prompt {i}" for i in range(5)]
        b = [f"ticket b prompt {i}" for i in range(3)]
        base = engine.answer_drained(a + b)
        ta = engine.submit(a)
        tb = engine.submit(b)
        engine.drain()
        assert engine.answers(ta) + engine.answers(tb) == base


class TestServingStats:
    def test_drained_partial_chunk_reports_dead_slots(self):
        eng = _make_engine()
        eng.stats = ServingStats()
        eng.answer_drained(["the only prompt of this chunk"])
        assert eng.stats.prefill_rows == 4
        assert eng.stats.live_prefill_rows == 1
        assert eng.stats.prefill_occupancy == 0.25
        # prefill_tokens counts only the real prompt's tokens
        assert eng.stats.prefill_tokens < 4 * eng.max_seq

    def test_sync_sites_by_discipline(self, engine):
        """Drained ticks serving_decode per step; continuous ticks
        serving_round once per scheduling round — both under
        SERVING_SITES, neither hidden from HOST_SYNCS."""
        prompts = [f"sync site probe {i}" for i in range(5)]
        before = dict(HOST_SYNCS.by_site)
        engine.answer_drained(prompts)
        mid = dict(HOST_SYNCS.by_site)
        assert mid.get("serving_decode", 0) > before.get(
            "serving_decode", 0)
        assert mid.get("serving_round", 0) == before.get(
            "serving_round", 0)
        engine.answer(prompts)
        after = dict(HOST_SYNCS.by_site)
        assert after.get("serving_round", 0) > mid.get(
            "serving_round", 0)
        assert after.get("serving_decode", 0) == mid.get(
            "serving_decode", 0)
        assert set(SERVING_SITES) == {"serving_round", "serving_decode"}

    def test_one_sync_per_round(self):
        """The continuous path's host fetches equal its scheduling
        rounds: done-masking happens on device, one packed fetch per
        round, with or without a decode step in it."""
        eng = _make_engine()
        eng.stats = ServingStats()
        before = HOST_SYNCS.site_total(SERVING_SITES)
        eng.answer([f"round sync probe {i}" for i in range(9)])
        delta = HOST_SYNCS.site_total(SERVING_SITES) - before
        assert delta == eng.stats.rounds
        assert 0 < eng.stats.decode_steps <= eng.stats.rounds

    def test_queue_latency_and_ttv(self):
        eng = _make_engine()
        eng.stats = ServingStats()
        eng.answer([f"latency probe {i}" for i in range(10)])
        assert len(eng.stats.ttv_s) == 10
        assert all(t > 0 for t in eng.stats.ttv_s)
        assert eng.stats.queued_peak >= 6  # 10 submitted, 4 slots
        assert eng.stats.queue_wait_max_s >= 0.0
        snap = eng.stats.snapshot()
        assert snap["ttv_p99_s"] >= snap["ttv_p50_s"] > 0


# ---------------------------------------------------------------------------
# Shared-cache front door: drained == continuous over the 44-query corpus
# ---------------------------------------------------------------------------

def _corpus_run(continuous):
    """Run every corpus query through a FrontDoor per schema, all
    sharing ONE engine-backed runner and ONE FunctionCache (shared
    scope: fresh_cache_per_query=False)."""
    eng = _make_engine(batch_size=16, max_seq=48)
    backend = ModelBackend.from_engine(eng, continuous=continuous)
    runner = SemanticRunner(backend)
    doors, dbs = {}, {}
    out = []
    for spec in ALL_QUERIES:
        if spec.schema not in doors:
            dbs[spec.schema] = SCHEMAS[spec.schema](seed=0, scale=0.15)
            doors[spec.schema] = FrontDoor(dbs[spec.schema], runner,
                                           n_lanes=2)
        db = doors[spec.schema]
        opt = optimize(spec.build(), dbs[spec.schema].catalog(),
                       strategy="cost")
        table, stats = db.execute(opt.plan)
        recs = dbs[spec.schema].materialize(table, list(spec.out_cols))
        out.append((spec.qid, recs, stats))
    return out, backend


def test_corpus_front_door_drained_vs_continuous():
    """All 44 corpus queries through the shared-cache front door:
    identical rows and identical llm_calls / cache_hits /
    pipeline_syncs whether the engine serves drained or continuous."""
    drained, bd = _corpus_run(continuous=False)
    cont, bc = _corpus_run(continuous=True)
    assert bd.calls == bc.calls
    for (qid_d, recs_d, sd), (qid_c, recs_c, sc) in zip(drained, cont):
        assert qid_d == qid_c
        assert result_f1(recs_d, recs_c) == 1.0, qid_d
        for f in ("llm_calls", "cache_hits", "null_skipped",
                  "probe_rows", "pipeline_syncs"):
            assert getattr(sd, f) == getattr(sc, f), (qid_d, f)
        # the continuous path still reports its serving-tier fetches
        assert sc.serving_syncs >= 0


# ---------------------------------------------------------------------------
# The first token comes from prefill
# ---------------------------------------------------------------------------

_MIXED = ["short", "a somewhat longer prompt with several more words",
          "a prompt of middling length", "the longest prompt of them all "
          "runs on for quite a few words before it ends"]


def _answering_params(eng, prompts):
    """The engine's params with the head's YES and NO columns leaning on
    the mean final-norm state at SEP of ``prompts``, so one of the two
    comes first at every one of them (as the benchmark's verdict head
    does), and split along their spread so both answers occur."""
    toks = np.stack([eng.encode_row(p)[0] for p in prompts])
    lens = np.asarray([eng.encode_row(p)[1] for p in prompts])
    _, h, _ = forward(_CFG, eng.policy, eng.params,
                      {"tokens": jnp.asarray(toks)})
    x = np.asarray(h, np.float64)[np.arange(len(prompts)), lens - 1]
    top = x.mean(0) / np.linalg.norm(x.mean(0))
    v = x[0] - x.mean(0)
    v -= v.dot(top) * top
    v /= np.abs((x - x.mean(0)).dot(v)).max()
    head = np.asarray(eng.params["lm_head"], np.float64)
    rest = np.abs(x.dot(head)).max()
    assert x.dot(top).min() > 0
    lean = (rest + 10.0) / x.dot(top).min()
    cols = np.stack([lean * top + v, lean * top - v], 1)
    head[:, [eng.tok.YES, eng.tok.NO]] = cols
    return dict(eng.params, lm_head=jnp.asarray(head, jnp.float32))


class TestPrefillFirstToken:
    def test_prefill_last_logits_match_forward_and_drained(self, engine):
        """``prefill(..., last=len - 1)`` gives the logits of each row's
        last real token whatever padding follows it: those of a full
        forward there, and those of the drained path's first step."""
        toks, lens = engine._encode_batch(_MIXED)
        assert len(set(lens.tolist())) == len(_MIXED)
        batch = {"tokens": jnp.asarray(toks)}
        got, _ = prefill(_CFG, engine.policy, engine.params, batch,
                         max_seq=engine.cache_len,
                         last=jnp.asarray(lens - 1))
        full, _, _ = forward(_CFG, engine.policy, engine.params, batch)
        rows = np.arange(len(_MIXED))
        np.testing.assert_allclose(got, full[rows, lens - 1],
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(got, engine.first_step_logits(_MIXED),
                                   rtol=1e-5, atol=1e-5)
        # without ``last`` prefill keeps the last padded position
        pad, _ = prefill(_CFG, engine.policy, engine.params, batch,
                         max_seq=engine.cache_len)
        np.testing.assert_allclose(pad, full[:, -1], rtol=1e-5, atol=1e-5)

    def test_answering_head_costs_no_decode_step(self):
        """A head that answers at every SEP: each request finishes at the
        token prefill emits, no decode step runs, and the tokens are the
        drained path's."""
        eng = _make_engine()
        prompts = [f"is item {i} of the catalogue in stock?"
                   for i in range(11)] + _MIXED
        eng.params = _answering_params(eng, prompts)
        eng.stats = ServingStats()
        out = eng.answer(prompts)
        assert eng.stats.decode_steps == 0
        assert eng.stats.prefill_answers == eng.stats.prompts == 15
        assert eng.stats.rounds > 0
        assert eng.stats.slot_steps == 0
        assert eng.stats.decode_tokens == 15
        assert set(out) == {"YES", "NO"}
        assert out == eng.answer_drained(prompts)
        snap = eng.stats.snapshot()
        assert (snap["rounds"], snap["prefill_answers"]) == (
            eng.stats.rounds, 15)

    @pytest.mark.parametrize("max_new", [2, 3])
    def test_multi_token_answers_match_drained(self, max_new):
        """With random weights a request runs past its first token: the
        later tokens are decoded, and all equal the drained path's."""
        eng = _make_engine(max_new=max_new)
        prompts = [f"multi token prompt {i}" for i in range(6)] + _MIXED
        eng.stats = ServingStats()
        ticket = eng.submit(prompts)
        eng.drain(ticket)
        ids = eng.scheduler.take(ticket)
        assert eng.stats.decode_steps > 0
        assert eng.stats.prefill_answers < len(prompts)
        assert max(len(t) for t in ids) == max_new
        assert [eng._detok(t) for t in ids] == eng.answer_drained(prompts)


class TestHashTokenizer:
    def test_stable_and_reserved(self):
        tok = HashTokenizer(1024)
        a = tok.encode("hello world", 8)
        b = tok.encode("hello world", 8)
        np.testing.assert_array_equal(a, b)
        assert a[0] == tok.BOS
        assert (a >= 0).all() and (a < 1024).all()
        # reserved ids never produced by hashing
        assert all(t >= tok.RESERVED or t == tok.BOS for t in a if t != 0)
