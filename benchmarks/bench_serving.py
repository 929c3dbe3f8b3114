"""Serving-tier benchmark: continuous slot scheduler vs drain-per-batch
under a Zipfian multi-query workload behind the shared-cache front door.

The workload is the "millions of users" shape the ROADMAP names: every
corpus query once (so drained↔continuous equivalence is held over all
44), then extra query instances Zipf-sampled from the same pool — hot
queries repeat, so the shared ``FunctionCache`` turns most of their
probes into hits and each semantic operator dispatches a *small* set of
distinct misses. That regime is exactly where drain-per-batch loses:
every miss chunk pads to ``batch_size`` prefill rows and pays one host
sync per decode step, while the continuous scheduler admits misses into
power-of-two buckets with zero dead prefill rows, interleaves prefill
with decode, and fetches one packed (emit ‖ finished) vector per
scheduling round.

    PYTHONPATH=src python benchmarks/bench_serving.py \
        [--extra 60] [--batch 4] [--zipf 1.1] [--smoke] [--json P]

Timing is steady-state: the full workload runs once untimed (warming
every jit the workload touches — one prefill shape per power-of-two
admission width, the decode round, the executor's data-path kernels),
the shared cache scope is cleared so the timed pass re-dispatches the
exact same misses, and only the second pass is timed.  The default
``--batch 4`` is the regime where drain-per-batch's blocking per-step
syncs dominate (2k+ sync points, zero dispatch overlap); at wider
batches the per-sync overhead amortises and the two disciplines
converge — the batch sweep is part of the recorded artifact.

Acceptance gates: continuous >= 1.3x drained tokens/s on the Zipfian
workload (full mode only — never timing in CI), and — deterministic,
so checked in smoke mode too — every query instance returns identical
rows and identical ``llm_calls`` / ``cache_hits`` / ``pipeline_syncs``
on both disciplines, with the serving tier's own fetches accounted
separately (``serving_syncs``; sites ``serving_round`` /
``serving_decode``). Both disciplines report p50/p99 time-to-verdict.
``--smoke`` shrinks the pool for CI; full-size runs additionally write
the repo-root ``BENCH_serving.json`` perf-trajectory snapshot that
``tools/check_docs.py`` verifies.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

import jax  # noqa: E402

from corpus import ALL_QUERIES  # noqa: E402

from repro.configs import get_tiny  # noqa: E402
from repro.core import optimize  # noqa: E402
from repro.data import SCHEMAS  # noqa: E402
from repro.engine import FrontDoor, result_f1  # noqa: E402
from repro.kernels.sync import HOST_SYNCS, SERVING_SITES  # noqa: E402
from repro.models import init_params  # noqa: E402
from repro.semantic import ModelBackend, SemanticRunner  # noqa: E402
from repro.serving.engine import ServingEngine, ServingStats  # noqa: E402
from repro.sharding.policy import ShardingPolicy  # noqa: E402
from repro.training.data import HashTokenizer  # noqa: E402

TOKENS_RATIO_MIN = 1.3


def build_workload(pool, extra: int, zipf_s: float, seed: int):
    """Every pool query once (the 44-query equivalence floor), then
    ``extra`` instances Zipf-sampled over the pool — rank r drawn with
    probability ∝ r^-s, the classic hot-query skew."""
    specs = list(pool)
    ranks = np.arange(1, len(pool) + 1, dtype=np.float64)
    probs = ranks ** -zipf_s
    probs /= probs.sum()
    rng = np.random.default_rng(seed)
    specs += [pool[i] for i in rng.choice(len(pool), size=extra, p=probs)]
    return specs


def make_engine(batch: int) -> ServingEngine:
    cfg = get_tiny("stablelm-3b").replace(vocab_size=512)
    params = init_params(cfg, jax.random.PRNGKey(0))
    return ServingEngine(cfg, params, ShardingPolicy.single(),
                         tokenizer=HashTokenizer(cfg.vocab_size),
                         batch_size=batch, max_seq=48, max_new_tokens=2)


def run_workload(specs, continuous: bool, batch: int,
                 repeats: int = 3):
    """One full pass: every query instance through a per-schema
    ``FrontDoor``, all doors sharing ONE engine-backed runner (one
    FunctionCache / VerdictTable, shared scope across queries)."""
    eng = make_engine(batch)
    backend = ModelBackend.from_engine(eng, continuous=continuous)
    runner = SemanticRunner(backend)
    doors, dbs, plans = {}, {}, {}

    for spec in specs:
        if spec.schema not in doors:
            dbs[spec.schema] = SCHEMAS[spec.schema](seed=0, scale=0.15)
            doors[spec.schema] = FrontDoor(dbs[spec.schema], runner,
                                           n_lanes=4)
        if spec.qid not in plans:
            plans[spec.qid] = optimize(
                spec.build(), dbs[spec.schema].catalog(),
                strategy="cost").plan

    # warm pass: run the FULL workload once untimed, which compiles
    # every jit this workload touches — the continuous scheduler's
    # per-power-of-two-width prefill shapes, the decode round, and the
    # executor's data-path kernels at these table sizes.  Then clear
    # the shared cache scope so the timed pass re-dispatches the exact
    # same misses, and time steady-state serving only.
    for spec in specs:
        doors[spec.schema].execute(plans[spec.qid])
    eng.drain()

    # timed passes: each identical (scope cleared first), best-of-N
    # wall clock so a scheduler hiccup doesn't decide the gate
    best = None
    for _ in range(max(1, repeats)):
        for door in doors.values():
            door.reset_scope()
        backend.reset_counters()
        eng.stats = ServingStats()
        HOST_SYNCS.reset()
        per_query = []
        lat = []
        t0 = time.perf_counter()
        for spec in specs:
            tq = time.perf_counter()
            table, stats = doors[spec.schema].execute(plans[spec.qid])
            lat.append(time.perf_counter() - tq)
            recs = dbs[spec.schema].materialize(table,
                                                list(spec.out_cols))
            per_query.append((spec.qid, recs, stats))
        wall = time.perf_counter() - t0

        s = eng.stats
        tokens = s.prefill_tokens + s.decode_tokens
        run = {
            "wall_s": wall,
            "tokens": tokens,
            "tokens_per_s": tokens / max(wall, 1e-12),
            "backend_calls": backend.calls,
            "per_query": per_query,
            "query_lat_p99_s": (float(np.percentile(lat, 99))
                                if lat else 0.0),
            "serving": s.snapshot(),
            "host_syncs": HOST_SYNCS.snapshot(),
        }
        if best is None or run["wall_s"] < best["wall_s"]:
            best = run
    return best


def check_equivalence(drained, cont) -> list[str]:
    """Verdict-for-verdict identity between the two disciplines: rows,
    llm_calls, cache_hits and pipeline_syncs per query instance."""
    errors = []
    if drained["backend_calls"] != cont["backend_calls"]:
        errors.append(f"backend calls differ: {drained['backend_calls']}"
                      f" vs {cont['backend_calls']}")
    for (qd, rd, sd), (qc, rc, sc) in zip(drained["per_query"],
                                          cont["per_query"]):
        if qd != qc:
            errors.append(f"query order diverged: {qd} vs {qc}")
            break
        if result_f1(rd, rc) != 1.0:
            errors.append(f"{qd}: rows differ")
        for f in ("llm_calls", "cache_hits", "null_skipped",
                  "probe_rows", "pipeline_syncs"):
            if getattr(sd, f) != getattr(sc, f):
                errors.append(f"{qd}: {f} {getattr(sd, f)} vs "
                              f"{getattr(sc, f)}")
    return errors


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--extra", type=int, default=60,
                    help="Zipf-sampled query instances beyond the pool")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--zipf", type=float, default=1.1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny workload; fail on crash/mismatch, not timing")
    ap.add_argument("--json", type=Path,
                    default=Path("artifacts/bench/BENCH_serving_tier.json"))
    args = ap.parse_args(argv)

    pool = list(ALL_QUERIES)
    if args.smoke:
        pool = pool[:8]
        args.extra = 6
    specs = build_workload(pool, args.extra, args.zipf, args.seed)
    n44 = len(pool)
    print(f"workload: {len(specs)} query instances "
          f"({n44} distinct pool queries + {args.extra} Zipf(s={args.zipf}) "
          f"repeats), batch={args.batch}")

    runs = {}
    for name, continuous in (("continuous", True), ("drained", False)):
        runs[name] = run_workload(specs, continuous, args.batch)
        r = runs[name]
        sv = r["serving"]
        ssync = sum(r["host_syncs"]["by_site"].get(s, 0)
                    for s in SERVING_SITES)
        print(f"{name:>11}: wall={r['wall_s']:.2f}s  "
              f"tokens/s={r['tokens_per_s']:.0f}  "
              f"prompts={sv['prompts']}  batches={sv['batches']}  "
              f"rounds={sv['rounds']}  "
              f"decode_steps={sv['decode_steps']}  "
              f"occupancy={sv['occupancy']:.2f}  "
              f"prefill_occupancy={sv['prefill_occupancy']:.2f}  "
              f"ttv_p50={sv['ttv_p50_s'] * 1e3:.2f}ms  "
              f"ttv_p99={sv['ttv_p99_s'] * 1e3:.2f}ms  "
              f"serving_syncs={ssync}")

    errors = check_equivalence(runs["drained"], runs["continuous"])
    for e in errors:
        print(f"EQUIVALENCE FAIL: {e}", file=sys.stderr)

    ratio = (runs["continuous"]["tokens_per_s"]
             / max(runs["drained"]["tokens_per_s"], 1e-12))
    print(f"\ntokens/s ratio (continuous / drained): {ratio:.2f}x  "
          f"(gate >= {TOKENS_RATIO_MIN}x, full mode)  "
          f"p99 time-to-verdict: continuous="
          f"{runs['continuous']['serving']['ttv_p99_s'] * 1e3:.2f}ms "
          f"drained={runs['drained']['serving']['ttv_p99_s'] * 1e3:.2f}ms")

    gated = not args.smoke
    ok = not errors and (not gated or ratio >= TOKENS_RATIO_MIN)
    out = {
        "name": "serving_tier",
        "command": "python benchmarks/bench_serving.py",
        "config": {"pool": n44, "extra": args.extra, "zipf": args.zipf,
                   "batch": args.batch, "seed": args.seed,
                   "smoke": args.smoke},
        "continuous": {k: v for k, v in runs["continuous"].items()
                       if k != "per_query"},
        "drained": {k: v for k, v in runs["drained"].items()
                    if k != "per_query"},
        "tokens_per_s_ratio": ratio,
        "equivalence_errors": errors,
        "gate": {"tokens_ratio_min": TOKENS_RATIO_MIN if gated else None,
                 "equivalence": not errors, "pass": ok},
    }
    args.json.parent.mkdir(parents=True, exist_ok=True)
    args.json.write_text(json.dumps(out, indent=2) + "\n")
    print(f"wrote {args.json}")
    if not args.smoke:
        root_json = Path(__file__).resolve().parent.parent \
            / "BENCH_serving.json"
        root_json.write_text(json.dumps(out, indent=2) + "\n")
        print(f"wrote {root_json}")

    if not ok:
        if gated and ratio < TOKENS_RATIO_MIN:
            print(f"FAIL: expected >= {TOKENS_RATIO_MIN}x tokens/s",
                  file=sys.stderr)
        return 1
    print("PASS" + ("" if gated else
                    " (smoke: crash/equivalence gates only)"))
    return 0


if __name__ == "__main__":
    sys.exit(main())
