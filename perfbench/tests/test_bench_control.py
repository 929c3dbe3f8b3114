"""The logit check's control at a CPU test's size: the reference computed
with int8 linear layers in the served model's place comes out not
correct, where the served bf16 model comes out correct. The limit on
the mean gap here lies between this size's readings (served at most
0.00106, int8 at least 0.00328 over six seeds, on a CPU), as the cell's
lies between the chip's (PERF.md)."""
from conftest import SEED, lm_config
from perfbench.harness import run_cell

CFG = {"ecommerce-stablelm-3b": lm_config(layers=4, width=256, ff=512,
                                          vocab=4096, limit=0.002,
                                          scale=0.5)}


def test_control_fails_where_the_served_model_passes(bench):
    r = run_cell("ecom-lm", SEED, 1.0, False, require_tpu=False,
                 bench=bench, configs=CFG, control=("int8",))
    served = r["checks"]["lm_logit_gap_mean_served"]["value"]
    control = r["checks"]["lm_logit_gap_mean"]["value"]
    assert served <= 0.002 < control
    assert not r["correct"]
