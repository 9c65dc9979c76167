"""Median time the loop waited for the loader's next batch and its copy to
the device (the harness's span around it), per step in the window."""

from stats import percentile


def read(run):
    w = sorted(s["input_wait_s"] for s in run.steps)
    return 1e3 * percentile(w, 0.5) if w else None
