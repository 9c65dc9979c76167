"""95th percentile gap between consecutive tokens, over every request due in
the window, as the client sees them (step return times)."""

from stats import gaps, percentile


def read(run):
    return 1e3 * percentile(
        gaps({r: t for r, t in run.token_times.items() if r in run.due}), 0.95)
