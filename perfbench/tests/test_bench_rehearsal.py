"""The whole harness, end to end on the CPU at small sizes, and the
entry's refusals."""
import json
import os
import shutil
import subprocess
import sys

from conftest import SEED, lm_config
from perfbench.harness import ROOT, cell_metrics, run_cell


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
