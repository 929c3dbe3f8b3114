"""The kernels readers on a synthetic run: each serving program's device
time against the work its requests need, and nothing where the program
is not on the trace."""
import pytest

from perfbench.harness import Run, read_metric
from perfbench.reference.lm import NO, YES
from perfbench.tracing import Spans, TraceSummary

MODEL = {"num_hidden_layers": 2, "hidden_size": 4,
         "num_attention_heads": 2, "num_key_value_heads": 2,
         "intermediate_size": 8, "vocab_size": 10}
# 2 x (16 + 32 + 16 + 96) matmul parameters a layer, and the 4 x 10 head
PARAMS = 360
# prompts of 5 and 4 tokens (BOS, words, SEP); one and two decode steps
SERVED = [("a b c", [YES]), ("x y", [7, NO])]


def _run(device_ops, served=SERVED, decode_steps=2):
    return Run(records=[], t0=0.0, t1=1.0, setup_s=0.0, peak_bytes=0,
               spans=Spans(), templates={}, table_rows={},
               peaks={"bf16_flops": 2e4, "hbm_bytes_per_s": 1e4},
               trace=TraceSummary(window_s=1.0, busy_s=0.75, chips=1,
                                  device_ops=device_ops),
               serving={"decode_steps": decode_steps}, served=served,
               model=MODEL)


OPS = [["jit__prefill_insert", 0.5], ["jit__decode_round", 0.25]]


def test_prefill_share_counts_real_prompt_tokens():
    # 2 x 360 FLOPs a token, and 4 x 2 layers x 4 wide a key: 5 tokens
    # over 15 keys, 4 over 10
    want = (9 * 2 * PARAMS + 32 * (15 + 10))
    got = read_metric({"name": "kernels.prefill_flops_share"}, _run(OPS))
    assert got == pytest.approx(100 * want / (0.5 * 2e4))


def test_decode_share_counts_weights_a_round_and_live_positions():
    # K and V, 2 layers, 2 heads of 2, 2 bytes: 32 bytes a position;
    # positions read: 5 by the first request, 4 + 5 by the second
    want = 2 * PARAMS * 2 + 32 * (5 + 9)
    got = read_metric({"name": "kernels.decode_hbm_share"}, _run(OPS))
    assert got == pytest.approx(100 * want / (0.25 * 1e4))


@pytest.mark.parametrize("name", ["kernels.prefill_flops_share",
                                  "kernels.decode_hbm_share"])
def test_nothing_without_the_program_or_requests(name):
    # a trace whose decode round is unnamed, as before it had a name
    unnamed = [["jit__unknown", 0.25]]
    assert read_metric({"name": name}, _run(unnamed)) is None
    assert read_metric({"name": name}, _run(OPS, served=[])) is None
    untraced = _run(OPS)
    untraced.trace = None
    assert read_metric({"name": name}, untraced) is None
