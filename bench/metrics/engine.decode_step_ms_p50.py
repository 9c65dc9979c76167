"""Median wall time of the engine's decode program call, the host sync on
its sampled tokens included, from the engine's decode stage records."""

from stats import percentile


def read(run):
    d = sorted(s["decode"][0] for s in run.steps if s["decode"])
    return 1e3 * percentile(d, 0.5) if d else None
