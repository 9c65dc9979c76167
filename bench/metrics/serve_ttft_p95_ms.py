"""95th percentile time to first token of every request due in the window,
from when it was due to when the step that emitted the token returned; a
request with no first token by the end of the drain counts at its elapsed
time."""

from stats import percentile, ttfts


def read(run):
    return 1e3 * percentile(ttfts(run.due, run.first, run.end_s), 0.95)
