"""The whole harness, end to end on the CPU at small sizes, and the
entry's refusals."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import SEED, lm_config
from perfbench import harness
from perfbench.harness import ROOT, Run, cell_metrics, read_metric, run_cell
from perfbench.tracing import Spans


def _cpu_env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **extra)
    env.pop("PYTHONPATH", None)
    return env


def test_entry_refuses_a_machine_without_a_tpu():
    p = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", "ecom-lm",
         "--seed", "5", "--seconds", "1", "--trace", "0"], cwd=ROOT,
        env=_cpu_env(), capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "{" not in p.stdout


def test_entry_refuses_a_tree_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "perfbench", tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", "ecom-lm",
         "--seed", "5", "--seconds", "1", "--trace", "0"], cwd=tmp_path,
        env=_cpu_env(), capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "{" not in p.stdout


def test_lm_cell_end_to_end(bench):
    cfgs = {"ecommerce-stablelm-3b": lm_config()}
    for trace in (False, True):
        r = run_cell("ecom-lm", SEED, 1.0, trace, require_tpu=False,
                     bench=bench, configs=cfgs)
        assert r["correct"] and r["failed"] == 0 and r["attempted"] >= 4
        assert r["attempted"] % 4 == 0  # whole cycles of q2 q4 q6 q9
        assert list(r)[-1] == "checks"
        assert set(r["checks"]) == {"queries_wrong", "lm_logit_gap_mean"}
        names = {m["name"] for m in cell_metrics(bench, "ecom-lm", trace)}
        assert set(r["metrics"]) <= names
        for m in r["metrics"].values():
            assert m["value"] >= 0 and m["unit"]
        assert r["device"]["platform"] == "cpu"
        if trace:
            # a CPU trace has no chip: device metrics read nothing
            assert "device.idle_share" not in r["metrics"]
            assert r["metrics"]["semantic.lm_calls_per_query"]["value"] > 0
            assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}
        else:
            assert {"queries_per_s", "query_p95_s", "setup_s"} <= set(
                r["metrics"])
    json.dumps(r)


def test_a_family_added_as_a_file_is_the_one_used(monkeypatch, bench):
    # a configuration names a family module that no file of the harness
    # knows of; the run is correct and went through that module
    loaded, real = [], harness.load_family

    def spy(config, root=ROOT):
        loaded.append(real(config, root))
        return loaded[-1]
    monkeypatch.setattr(harness, "load_family", spy)
    cfg = lm_config()
    cfg["family"] = "perfbench/tests/counting_family.py"
    r = run_cell("ecom-lm", SEED, 0.5, False, require_tpu=False,
                 bench=bench, configs={"ecommerce-stablelm-3b": cfg})
    assert r["correct"] and r["attempted"] >= 4
    (family,) = loaded
    assert family.__file__.endswith("counting_family.py")
    calls = family.CALLS
    # the program's config, the weights for the program and again for
    # the reference, the verdict head's and the logit check's forwards
    assert calls["program_config"] == 1 and calls["init_weights"] == 2
    assert calls["forward_hidden"] >= 2
    # the readers count a request's FLOPs through the run's family
    served = [("a b c", [2]), ("x y", [7, 3])]
    run = Run(records=[], t0=0.0, t1=1.0, setup_s=0.0, peak_bytes=0,
              spans=Spans(), templates={}, table_rows={},
              peaks={"bf16_flops": 1e12}, served=served, model=cfg["model"],
              family=family)
    assert read_metric({"name": "serving.mfu"}, run) > 0
    assert calls["request_flops"] == len(served)


def test_an_lm_configuration_without_a_family_is_refused(bench):
    cfg = lm_config()
    del cfg["family"]
    with pytest.raises(ValueError, match="no \"family\" key"):
        run_cell("ecom-lm", SEED, 0.5, False, require_tpu=False,
                 bench=bench, configs={"ecommerce-stablelm-3b": cfg})
