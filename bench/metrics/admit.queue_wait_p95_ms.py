"""95th percentile wait from arrival to admission, from the engine's prefill
stage records (the admitting step's ``now`` minus the arrival time)."""

from stats import percentile


def read(run):
    waits = sorted(p[3] for s in run.steps for p in s["prefills"]
                   if p[3] is not None)
    return 1e3 * percentile(waits, 0.95) if waits else None
