"""The prefill program's share of the chip's bf16 peak, in %: the model
FLOPs of prefilling every request the window served, at its real prompt
length (the configuration's family module, ``request_flops``), over the
device time of the program ``jit__prefill_insert`` on the trace times
the peak. Padding counts nothing, so a change that stops computing it
cannot push the share past 100%. This is the compute roof only: a
launch of a few rows is bound by reading the weights, which this share
does not credit."""
from perfbench.reference.lm import prompt_ids

PROGRAM = "jit__prefill_insert"


def read(run):
    """Useful prefill FLOP/s over the peak; nothing without the program
    on the trace, a served LM request or a peak."""
    seconds = sum(d for n, d in run.trace.device_ops
                  if n == PROGRAM) if run.trace is not None else 0.0
    if not seconds or not run.served or not run.peaks or run.family is None:
        return None
    vocab = run.model["vocab_size"]
    flops = sum(run.family.request_flops(run.model,
                                         len(prompt_ids(p, vocab)), 0)
                for p, _ in run.served)
    return 100.0 * flops / (seconds * run.peaks["bf16_flops"])
