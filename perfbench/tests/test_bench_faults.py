"""``correct`` comes out false when the timed path is broken underneath:
an answer or a token altered where it is produced, half of each result
left out, or a filter that lets rows through that it should not."""
import operator

import jax.numpy as jnp
import pytest

from conftest import SEED, lm_config, oracle_bench, oracle_configs
from perfbench import plans
from perfbench.harness import run_cell
from repro.engine import FrontDoor
from repro.semantic import ModelBackend, OracleBackend
from repro.serving.engine import ServingEngine


def _lm_run(bench):
    return run_cell("ecom-lm", SEED, 0.5, False, require_tpu=False,
                    bench=bench, configs={"ecommerce-stablelm-3b":
                                          lm_config()})


def _oracle_run():
    return run_cell("ecom-oracle", SEED, 0.5, False, require_tpu=False,
                    bench=oracle_bench(), configs=oracle_configs())


def _rows_wrong(r):
    return not r["correct"] and r["checks"]["queries_wrong"]["value"] > 0


@pytest.mark.parametrize("cell", ["ecom-lm", "ecom-oracle"])
def test_sound_run_is_correct(cell, bench):
    r = _lm_run(bench) if cell == "ecom-lm" else _oracle_run()
    assert r["correct"] and r["checks"]["queries_wrong"]["value"] == 0


def test_oracle_answer_altered(monkeypatch):
    real = OracleBackend.evaluate_batch

    def flip_first(self, prompts, contexts):
        out = real(self, prompts, contexts)
        if out:
            out[0] = not out[0]
        return out
    monkeypatch.setattr(OracleBackend, "evaluate_batch", flip_first)
    assert _rows_wrong(_oracle_run())


def test_lm_verdict_altered_where_it_is_parsed(monkeypatch, bench):
    real = ModelBackend._parse
    seen = []

    def flip_some(self, r, ctx):
        v = real(self, r, ctx)
        seen.append(v)
        return (not v) if len(seen) % 7 == 0 else v
    monkeypatch.setattr(ModelBackend, "_parse", flip_some)
    assert _rows_wrong(_lm_run(bench))


@pytest.mark.parametrize("cell", ["ecom-lm", "ecom-oracle"])
def test_half_of_each_result_left_out(monkeypatch, cell, bench):
    real = FrontDoor.execute

    def half(self, plan):
        table, stats = real(self, plan)
        n = table.capacity
        keep = jnp.arange(n) < n // 2
        return table.with_mask(keep), stats
    monkeypatch.setattr(FrontDoor, "execute", half)
    assert _rows_wrong(_lm_run(bench) if cell == "ecom-lm"
                       else _oracle_run())


def test_filter_lets_extra_rows_through(monkeypatch, bench):
    # the plan the program runs keeps rows one above each "<=" bound
    monkeypatch.setitem(plans.COMPARE, "<=",
                        lambda c, v: operator.le(c, v + 1))
    assert _rows_wrong(_lm_run(bench))


def test_served_token_altered(monkeypatch, bench):
    real = ServingEngine.answers

    def alter(self, ticket):
        out = real(self, ticket)
        ids = [w for w in out[0].split() if w.startswith("<")]
        if ids:
            t = int(ids[0].strip("<>"))
            out[0] = out[0].replace(ids[0], f"<{8 + (t + 97) % 400}>", 1)
        else:
            out[0] = "<100> " + out[0]
        return out
    monkeypatch.setattr(ServingEngine, "answers", alter)
    r = _lm_run(bench)
    assert not r["correct"]
    assert r["checks"]["lm_logit_gap_mean"]["value"] > \
        r["checks"]["lm_logit_gap_mean"]["limit"]
