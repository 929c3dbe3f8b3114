"""End-to-end driver (the paper's kind = serving): hybrid queries whose
semantic operators are answered by a REAL JAX model served with batched
requests — no oracle in the execution path.

    PYTHONPATH=src python examples/serve_semantic_queries.py

Pipeline: train (or reuse) the 13M-param backend from
examples/train_backend.py -> wrap it in ServingEngine (continuous slot
scheduler: prefill/decode interleaving, mid-decode slot recycling —
docs/serving.md) -> ModelBackend parses YES/NO -> PLOP
optimizes placement -> the executor sends only *distinct uncached* prompts
to the model. Reports accuracy vs. the noise-free oracle plus serving and
cache statistics.
"""
import time

import jax

from repro.core import Q, col, optimize
from repro.data import make_ecommerce
from repro.data.schemas import (
    ECOM_REVIEW_POSITIVE,
    PRODUCT_IS_ELECTRONICS,
)
from repro.engine import Executor, result_f1
from repro.semantic import ModelBackend, OracleBackend, SemanticRunner
from repro.serving.engine import ServingEngine
from repro.sharding import ShardingPolicy
from repro.training.checkpoint import CheckpointManager
from repro.training.data import HashTokenizer

import sys
sys.path.insert(0, "examples")
from train_backend import backend_config  # noqa: E402
from train_backend import main as train_backend_main  # noqa: E402


def get_backend_params():
    mgr = CheckpointManager("artifacts/backend_ckpt")
    if mgr.latest_step() is None:
        print("[serve] no backend checkpoint — training one (300 steps)")
        train_backend_main(["--steps", "300"])
    tree, manifest = mgr.restore()
    print(f"[serve] backend checkpoint: step={manifest['step']} "
          f"trained-accuracy={manifest.get('accuracy'):.3f}")
    return jax.tree.map(jax.numpy.asarray, tree["params"])


def main():
    cfg = backend_config()
    params = get_backend_params()
    policy = ShardingPolicy.single()
    tok = HashTokenizer(cfg.vocab_size)
    engine = ServingEngine(cfg, params, policy, tokenizer=tok,
                           batch_size=32, max_seq=48, max_new_tokens=2)
    db = make_ecommerce(seed=4)
    catalog = db.catalog()

    plan = (Q.scan("products")
            .join(Q.scan("previews"), "products.product_id",
                  "previews.product_id")
            .where(col("previews.rating") >= 4)
            .sem_filter(PRODUCT_IS_ELECTRONICS)
            .sem_filter(ECOM_REVIEW_POSITIVE)
            .select("products.title", "previews.review_id")
            .build())

    # oracle reference (ground truth)
    oracle_runner = SemanticRunner(OracleBackend(truths=db.truths))
    ref_table, _ = Executor(db, oracle_runner).execute(plan)
    ref = db.materialize(ref_table, ["products.title", "previews.review_id"])

    for strategy in ("none", "cost"):
        opt = optimize(plan, catalog, strategy=strategy)
        # bucket-aligned chunked dispatch: runner streams distinct misses
        # in multiples of the engine's serving batch
        backend = ModelBackend.from_engine(engine)
        runner = SemanticRunner(backend)
        ex = Executor(db, runner)
        t0 = time.perf_counter()
        table, stats = ex.execute(opt.plan)
        wall = time.perf_counter() - t0
        recs = db.materialize(table, ["products.title",
                                      "previews.review_id"])
        f1 = result_f1(ref, recs)
        print(f"\n=== strategy={strategy} (real model serving) ===")
        print(f"rows={len(recs)} (oracle says {len(ref)})  "
              f"F1 vs oracle={f1:.3f}")
        print(f"distinct model calls={stats.llm_calls}  "
              f"cache hits={stats.cache_hits}  wall={wall:.1f}s")
        print(f"serving: {engine.stats.batches} batches, "
              f"{engine.stats.decode_steps} decode steps, "
              f"{engine.stats.prefill_answers} answered at prefill, "
              f"{engine.stats.prefill_tokens} prefill tokens, "
              f"occupancy={engine.stats.occupancy:.2f}")


if __name__ == "__main__":
    main()
