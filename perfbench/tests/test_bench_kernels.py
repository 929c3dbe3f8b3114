"""The kernels and serving readers on a synthetic run: the prefill
program's device time against the work its requests need, the real
share of the launched prefill positions, and nothing where there is
nothing to read."""
import pytest

from perfbench.harness import Run, read_metric
from perfbench.reference import dense
from perfbench.reference.lm import NO, YES
from perfbench.tracing import Spans, TraceSummary

MODEL = {"num_hidden_layers": 2, "hidden_size": 4,
         "num_attention_heads": 2, "num_key_value_heads": 2,
         "intermediate_size": 8, "vocab_size": 10}
# 2 x (16 + 32 + 16 + 96) matmul parameters a layer, and the 4 x 10 head
PARAMS = 360
# prompts of 5 and 4 tokens (BOS, words, SEP); one and two answer tokens
SERVED = [("a b c", [YES]), ("x y", [7, NO])]


def _run(device_ops, served=SERVED, serving=None):
    return Run(records=[], t0=0.0, t1=1.0, setup_s=0.0, peak_bytes=0,
               spans=Spans(), templates={}, table_rows={},
               peaks={"bf16_flops": 2e4, "hbm_bytes_per_s": 1e4},
               trace=TraceSummary(window_s=1.0, busy_s=0.75, chips=1,
                                  device_ops=device_ops),
               serving=serving, served=served, model=MODEL, family=dense)


OPS = [["jit__prefill_insert", 0.5]]


def test_prefill_share_counts_real_prompt_tokens():
    # 2 x 360 FLOPs a token, and 4 x 2 layers x 4 wide a key: 5 tokens
    # over 15 keys, 4 over 10
    want = (9 * 2 * PARAMS + 32 * (15 + 10))
    got = read_metric({"name": "kernels.prefill_flops_share"}, _run(OPS))
    assert got == pytest.approx(100 * want / (0.5 * 2e4))


def test_mfu_counts_prefill_and_the_decode_passes_after_the_first_token():
    # prefill as above; the second request's second token is one decode
    # pass, at position 4, over 5 keys
    want = (9 * 2 * PARAMS + 32 * (15 + 10)) + (2 * PARAMS + 32 * 5)
    got = read_metric({"name": "serving.mfu"}, _run(OPS))
    assert got == pytest.approx(100 * want / (1.0 * 2e4))


def test_prefill_token_share_by_hand():
    # two launches of 3 rows x max_seq 8, holding 9 + 11 real tokens
    serving = {"prefill_tokens": 20, "prefill_token_slots": 48}
    got = read_metric({"name": "serving.prefill_token_share"},
                      _run(OPS, serving=serving))
    assert got == pytest.approx(100 * 20 / 48)


@pytest.mark.parametrize("serving", [None, {}, {"prefill_tokens": 0,
                                                "prefill_token_slots": 0}])
def test_prefill_token_share_nothing_without_serving(serving):
    # the oracle backend (no serving counts) or a window with no launch
    assert read_metric({"name": "serving.prefill_token_share"},
                       _run(OPS, serving=serving)) is None


@pytest.mark.parametrize("name", ["kernels.prefill_flops_share"])
def test_nothing_without_the_program_or_requests(name):
    # a trace whose prefill program is unnamed
    unnamed = [["jit__unknown", 0.25]]
    assert read_metric({"name": name}, _run(unnamed)) is None
    assert read_metric({"name": name}, _run(OPS, served=[])) is None
    untraced = _run(OPS)
    untraced.trace = None
    assert read_metric({"name": name}, untraced) is None
