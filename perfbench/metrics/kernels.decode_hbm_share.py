"""The decode round's share of the chip's HBM bandwidth, in %: the bytes
the window's rounds must read over the device time of the program
``jit__decode_round`` on the trace times the peak bandwidth. A round
reads every weight that takes part in a matmul (``perfbench/flops.py``)
once, and each live slot's keys and values up to its real position: a
request of ``n`` prompt tokens decodes its ``j``-th token at position
``n - 1 + j``, over ``n + j`` positions. Padding and finished slots
count nothing. Decode is bound by these bytes: at peak its FLOPs take a
tenth of their time or less."""
from perfbench.flops import lm_matmul_params
from perfbench.reference.lm import prompt_ids

PROGRAM = "jit__decode_round"
# bytes per weight and per cache entry: the benchmark serves bf16
# weights (systems.build) and the cache takes the weights' dtype
BYTES = 2


def read(run):
    """Bytes the rounds need over the peak; nothing without the program
    on the trace, a decode round or a peak."""
    seconds = sum(d for n, d in run.trace.device_ops
                  if n == PROGRAM) if run.trace is not None else 0.0
    if (not seconds or not run.serving or not run.serving["decode_steps"]
            or not run.served or not run.peaks or run.model is None):
        return None
    m = run.model
    head_dim = m["hidden_size"] // m["num_attention_heads"]
    kv_per_position = (2 * m["num_hidden_layers"]
                       * m["num_key_value_heads"] * head_dim * BYTES)
    positions = 0
    for p, toks in run.served:
        n, t = len(prompt_ids(p, m["vocab_size"])), len(toks)
        positions += t * n + t * (t - 1) // 2
    weights = run.serving["decode_steps"] * lm_matmul_params(m) * BYTES
    need = weights + positions * kv_per_position
    return 100.0 * need / (seconds * run.peaks["hbm_bytes_per_s"])
