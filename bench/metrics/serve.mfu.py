"""Model FLOP/s utilization of the serve window: the forward operations of
every token the window's steps produced (each prompt's causal prefill with
its last-position head; one token per active slot per decode step over its
cache) over the window and the chip's peak."""

import numpy as np

from flops import prefill_flops, token_flops


def read(run):
    if not run.peaks or not run.steps:
        return None
    total = 0.0
    for s in run.steps:
        total += sum(prefill_flops(run.family, run.cfg, p[1]) for p in s["prefills"])
        if s["decode"]:
            total += token_flops(run.family, run.cfg, np.asarray(s["decode"][2]) + 1)
    return 100.0 * total / (run.window_s * run.peaks["bf16_flops_per_s"])
