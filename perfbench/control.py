"""Readings of the LM logit check, for setting its limit.

    python3 perfbench/control.py --workload ecom-lm --seeds 11,12,13 \
        --seconds 5 [--control int8[,fp8]] [--sample 192]

Runs the cell's timed path on each seed in one process (a short window
at the cell's own load), then the plain reference over a sample of the
served requests. Without ``--control`` it prints the served tokens' mean
gap below the reference's best (the lower reading); with it, the mean
gap of the token the reference computed in int8 puts first, at the same
positions (the control, whose smallest reading is the upper one), for
each precision named. ``--sample`` sets how many served requests the
check re-runs; standard error gives the widest and the mean gap over the
first 192, 384, ... of them. One JSON line per seed, on a TPU only.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    """Run each seed and print its readings."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--control", default="",
                    help="comma-separated precisions: int8, fp8")
    ap.add_argument("--sample", type=int, default=0,
                    help="served requests to re-run (default: the cell's)")
    args = ap.parse_args(argv)
    from perfbench.harness import LM_SAMPLE, configure_cache, run_cell
    configure_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        r = run_cell(args.workload, seed, args.seconds, False,
                     control=tuple(filter(None, args.control.split(","))),
                     lm_sample=args.sample or LM_SAMPLE)
        print(json.dumps({"seed": seed, "correct": r["correct"],
                          "attempted": r["attempted"],
                          "checks": r["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    sys.exit(main())
