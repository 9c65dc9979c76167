"""Median wall time of one prompt's prefill program call, the host sync on
its first token included, from the engine's prefill stage records."""

from stats import percentile


def read(run):
    d = sorted(p[2] for s in run.steps for p in s["prefills"])
    return 1e3 * percentile(d, 0.5) if d else None
