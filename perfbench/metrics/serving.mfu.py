"""The serving tier's whole-step share of the chip's bf16 peak, in %:
model FLOPs of every request the window served (``perfbench/flops.py``,
at each request's real prompt and answer lengths) over the window's
seconds times the peak."""
from perfbench.flops import lm_request_flops
from perfbench.reference.lm import prompt_ids


def read(run):
    """Served model FLOP/s over the peak; nothing without an LM or peak."""
    if not run.served or not run.peaks or run.model is None:
        return None
    vocab = run.model["vocab_size"]
    flops = sum(lm_request_flops(run.model, len(prompt_ids(p, vocab)),
                                 len(toks)) for p, toks in run.served)
    return 100.0 * flops / (run.window_s * run.peaks["bf16_flops"])
