"""The serving tier's whole-step share of the chip's bf16 peak, in %:
model FLOPs of every request the window served (the configuration's
family module, ``request_flops``, at each request's real prompt and
answer lengths) over the window's seconds times the peak."""
from perfbench.reference.lm import prompt_ids


def read(run):
    """Served model FLOP/s over the peak; nothing without an LM or peak."""
    if not run.served or not run.peaks or run.family is None:
        return None
    vocab = run.model["vocab_size"]
    flops = sum(run.family.request_flops(
        run.model, len(prompt_ids(p, vocab)), len(toks))
        for p, toks in run.served)
    return 100.0 * flops / (run.window_s * run.peaks["bf16_flops"])
