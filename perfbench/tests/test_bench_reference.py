"""The plain references agree with the program where the program is
right, at sizes a CPU test holds."""
import hashlib

import jax
import numpy as np
import pytest

import conftest
from perfbench import data as datagen
from perfbench.data import ecommerce
from perfbench.plans import build_plan, load_templates
from perfbench.reference import dense, lm
from perfbench.reference.relational import Relational, mismatch
from repro.core import optimize
from repro.engine import Database, FrontDoor
from repro.semantic import OracleBackend, SemanticRunner

SEED = 2**31 + 11


def _setup(schema, scale):
    d = datagen.generate(schema, SEED, scale)
    db = Database()
    d.load(db)
    front = FrontDoor(db, SemanticRunner(OracleBackend(truths=db.truths)))
    return d, db, db.catalog(), front


@pytest.fixture(scope="module")
def ecom_db():
    return _setup("ecommerce", 1.0)


def _run(setup, tpl):
    d, db, cat, front = setup
    front.reset_scope()
    plan = optimize(build_plan(tpl, d.prompts), cat, strategy="cost").plan
    table, _ = front.execute(plan)
    return db.materialize(table, tpl["out"])


@pytest.mark.parametrize("name", ["q2", "q4", "q6", "q9"])
def test_ecommerce_rows_match_latent_and_recorded_verdicts(ecom_db, name):
    tpl = load_templates("ecommerce")[name]
    rows = _run(ecom_db, tpl)
    ref = Relational(ecom_db[0], ecommerce.LATENT)
    assert mismatch(rows, tpl["out"], ref.answer(tpl)) == ""
    # verdicts as the LM cells record them: every rendered prompt's truth
    d = ecom_db[0]
    verdicts = {}
    for pname, (tables, fn) in ecommerce.LATENT.items():
        t = tables[0]
        vals = fn({c: v for c, v in d.tables[t].items()})
        for i, v in enumerate(vals):
            rel = {t: np.asarray([i])}
            verdicts[ref.render(d.prompts[pname], rel, 0)] = bool(v)
    assert mismatch(rows, tpl["out"], ref.answer(tpl, verdicts)) == ""
    # a verdict the program never got is a fault, not a pass
    assert "lack a served verdict" in mismatch(rows, tpl["out"],
                                               ref.answer(tpl, {}))


def test_mismatch_catches_changed_and_dropped_rows(ecom_db):
    tpl = load_templates("ecommerce")["q4"]
    rows = _run(ecom_db, tpl)
    ans = Relational(ecom_db[0], ecommerce.LATENT).answer(tpl)
    assert mismatch(rows[: len(rows) // 2], tpl["out"], ans)
    bad = [dict(r) for r in rows]
    bad[0]["previews.review_id"] = -1
    assert mismatch(bad, tpl["out"], ans)


def test_limit_accepts_any_rows_of_the_full_answer(ecom_db):
    tpl = {"out": ["previews.review_id", "previews.rating"],
           "plan": [["scan", "previews"],
                    ["where", "previews.rating", "<=", 2], ["limit", 100],
                    ["select", "previews.review_id", "previews.rating"]]}
    ans = Relational(ecom_db[0], ecommerce.LATENT).answer(tpl)
    assert ans.limit == 100 and len(ans) > 100
    cols = tpl["out"]
    full = [dict(zip(cols, (int(a), int(b)))) for a, b in
            zip(*(c[-100:] for c in ans.cols))]
    assert mismatch(full, cols, ans) == ""
    assert mismatch(full[:-1], cols, ans)
    assert mismatch(_run(ecom_db, tpl), cols, ans) == ""


TINY = {"num_hidden_layers": 2, "hidden_size": 64, "num_attention_heads": 4,
        "num_key_value_heads": 4, "intermediate_size": 128,
        "vocab_size": 512, "layer_norm_eps": 1e-5, "rope_theta": 10000}


def test_lm_reference_matches_the_served_engine():
    from perfbench.harness import sample_prompts
    from repro.serving.engine import ServingEngine
    from repro.sharding.policy import ShardingPolicy
    d = datagen.generate("ecommerce", SEED, 0.05)
    groups = sample_prompts(d, load_templates("ecommerce"), 16)
    prompts = [p for g in groups for p in g]
    n = max(len(lm.prompt_ids(p, 512)) for p in prompts)
    w = dense.init_weights(TINY, SEED)
    w = lm.with_head(w, lm.verdict_head(dense, TINY, w, groups, SEED, n + 1))
    eng = ServingEngine(dense.program_config(TINY, "tiny"), w,
                        ShardingPolicy.single(), max_seq=n)
    for p in prompts:
        toks, k = eng.encode_row(p)
        assert list(toks[:k]) == lm.prompt_ids(p, 512)
    # prefill, then one decode step through the cache, against the plain
    # forward at each prompt's SEP: within bf16's rounding of logits
    # whose spread is VERDICT_SPREAD
    batch = prompts[:eng.batch_size]
    seqs = [lm.prompt_ids(p, 512) for p in batch]
    ref = np.asarray(lm.forward_logits(
        dense, TINY, w, lm._pad(seqs, n + 1),
        (np.arange(len(seqs)), np.asarray([len(q) - 1 for q in seqs]))))
    got = np.asarray(eng.first_step_logits(batch), np.float32)
    assert np.abs(got - ref).max() < 0.05 * np.abs(ref).max()
    served = [(p, lm.answer_ids(a)) for p, a in zip(prompts,
                                                    eng.answer(prompts))]
    assert all(t in ([lm.YES], [lm.NO]) for _, t in served)
    gaps = lm.served_gaps(dense, TINY, w, served, length=n + 1)
    assert len(gaps) == len(served)
    assert gaps.max() < 0.1 * lm.VERDICT_SPREAD


def test_verdict_head_answers_at_once_and_about_half_yes():
    from perfbench.harness import sample_prompts
    d = datagen.generate("ecommerce", SEED, 0.5)
    groups = sample_prompts(d, load_templates("ecommerce"), 300)
    length = 1 + max(len(lm.prompt_ids(p, 512)) for g in groups for p in g)
    w = dense.init_weights(TINY, SEED)
    head = lm.verdict_head(dense, TINY, w, [g[:64] for g in groups], SEED,
                           length)
    w = lm.with_head(w, head)
    for g in groups:
        # prompts the head was not chosen from
        seqs = [lm.prompt_ids(p, 512) for p in g[64:]]
        tokens = np.zeros((len(seqs), length), np.int32)
        for r, ids in enumerate(seqs):
            tokens[r, :len(ids)] = ids
        logits = lm.forward_logits(
            dense, TINY, w, tokens, (np.arange(len(seqs)),
                              np.asarray([len(q) - 1 for q in seqs])))
        first = np.asarray(logits.argmax(-1))
        assert set(first.tolist()) <= {lm.YES, lm.NO}
        assert 0.25 < np.mean(first == lm.YES) < 0.75


# sha256 of the conftest configuration's weights (each leaf's path, dtype
# and bytes, in tree order) and of its verdict head, drawn from conftest's
# SEED as a run draws them, before the dense family left reference/lm.py
PINNED_WEIGHTS = \
    "e4dadefb43853d83d96b76aaf72fc5da64444540711800b3c10397d8b489be3c"
PINNED_HEAD = \
    "01649fb639e8ec5c0d2011a01c07b8c94a6d055f66b3e031f2fcc470813ba390"


def test_weights_and_verdict_head_are_pinned():
    from perfbench.harness import (HEAD_SAMPLE, load_family,
                                   longest_prompt_tokens, sample_prompts)
    cfg, seed = conftest.lm_config(), conftest.SEED
    d = datagen.generate(cfg["schema"], cfg["data_seed"],
                         cfg["scale"]).permuted(seed)
    templates = load_templates("ecommerce")
    longest = longest_prompt_tokens(d, templates)
    family = load_family(cfg)
    w = family.init_weights(cfg["model"], seed)
    head = lm.verdict_head(family, cfg["model"], w,
                           sample_prompts(d, templates, HEAD_SAMPLE),
                           seed, length=longest + 1)
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_leaves_with_path(w):
        a = np.asarray(leaf)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(a.dtype).encode())
        h.update(a.tobytes())
    assert h.hexdigest() == PINNED_WEIGHTS
    assert head.dtype == np.float32 and head.shape == (64, 2)
    assert hashlib.sha256(head.tobytes()).hexdigest() == PINNED_HEAD
