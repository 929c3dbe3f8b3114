"""Small-size cells for rehearsing the harness on the CPU."""
import copy

import pytest

from perfbench.harness import ROOT, load_json

SEED = 2**31 + 21


def lm_config(layers=2, width=64, ff=128, vocab=512, limit=0.01,
              scale=0.1):
    """The ecommerce-stablelm-3b configuration at a CPU test's size."""
    cfg = load_json(ROOT / "perfbench/configs/ecommerce-stablelm-3b.json")
    cfg["model"].update(num_hidden_layers=layers, hidden_size=width,
                        num_attention_heads=4, num_key_value_heads=4,
                        intermediate_size=ff, vocab_size=vocab)
    cfg["scale"] = scale
    cfg["lm_logit_gap_mean_limit"] = limit
    return cfg


def oracle_bench():
    """BENCHMARK.json with a cell ``ecom-oracle``: the e-commerce traffic
    answered by the program's oracle backend, whatever the file holds."""
    bench = copy.deepcopy(load_json(ROOT / "BENCHMARK.json"))
    bench["configs"].append({"name": "ecommerce-oracle"})
    bench["workloads"].append({"name": "ecom-oracle",
                               "config": "ecommerce-oracle",
                               "traffic": "ecom-cold", "chips": 1})
    return bench


def oracle_configs(scale=0.5):
    """The e-commerce data with its verdicts from the latent truth."""
    cfg = load_json(ROOT / "perfbench/configs/ecommerce-stablelm-3b.json")
    return {"ecommerce-oracle": {"name": "ecommerce-oracle",
                                 "schema": "ecommerce", "scale": scale,
                                 "data_seed": cfg["data_seed"],
                                 "backend": "oracle"}}


@pytest.fixture
def bench():
    """The committed BENCHMARK.json."""
    return load_json(ROOT / "BENCHMARK.json")
