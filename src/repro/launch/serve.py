"""Serving driver: stand up a semantic backend and answer prompts or run
a hybrid query end to end.

    # answer ad-hoc prompts with the trained 13M backend
    PYTHONPATH=src python -m repro.launch.serve \
        --ckpt artifacts/backend_ckpt --prompts "is product 3 electronics?"

    # random bf16 weights drawn from --seed (no checkpoint needed); drop
    # --tiny for the published widths
    PYTHONPATH=src python -m repro.launch.serve --arch stablelm-3b --tiny \
        --seed 0 --prompts "hello" "world"
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp

from ..configs import get_config, get_tiny
from ..models import init_params
from ..serving.engine import ServingEngine, prompt_tokens
from ..sharding.policy import ShardingPolicy
from ..training.checkpoint import CheckpointManager
from ..training.data import HashTokenizer
from .compile_cache import enable_compile_cache
from .mesh import make_mesh


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmoe-1b-7b")
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--ckpt", default=None,
                    help="checkpoint dir (e.g. artifacts/backend_ckpt)")
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--max-seq", type=int, default=None,
                    help="prompt tokens per slot (default: longest prompt)")
    ap.add_argument("--dp", type=int, default=1)
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights (no --ckpt)")
    ap.add_argument("--prompts", nargs="+", required=True)
    args = ap.parse_args(argv)

    enable_compile_cache()
    if args.ckpt:
        import sys
        sys.path.insert(0, "examples")
        from train_backend import backend_config

        cfg = backend_config()
        tree, manifest = CheckpointManager(args.ckpt).restore()
        params = jax.tree.map(jnp.asarray, tree["params"])
        print(f"[serve] restored {cfg.name} @ step {manifest['step']}")
    else:
        cfg = get_tiny(args.arch) if args.tiny else get_config(args.arch)
        params = init_params(cfg, jax.random.PRNGKey(args.seed),
                             dtype=jnp.bfloat16)
        print(f"[serve] random bf16 weights for {cfg.name}, "
              f"seed {args.seed}")

    mesh = make_mesh(args.dp, args.tp)
    policy = (ShardingPolicy.for_mesh(mesh) if mesh.size > 1
              else ShardingPolicy.single())
    max_seq = args.max_seq or max(prompt_tokens(p) for p in args.prompts)
    print(f"[serve] max_seq {max_seq} tokens")
    engine = ServingEngine(cfg, params, policy,
                           tokenizer=HashTokenizer(cfg.vocab_size),
                           batch_size=args.batch, max_seq=max_seq)
    answers = engine.answer(args.prompts)
    for p, a in zip(args.prompts, answers):
        print(f"  {p!r} -> {a}")
    s = engine.stats
    print(f"[serve] {s.prompts} prompts, {s.batches} batches, "
          f"{s.decode_steps} decode steps, {s.prefill_answers} answered "
          f"at prefill, {s.wall_s:.2f}s")


if __name__ == "__main__":
    main()
