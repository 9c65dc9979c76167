"""Median time the train loop blocked on the loader's queue for its next
batch: the program's own ``repro.loader.wait`` span (``BatchLoader.batches``),
from the traced window on the device trace's clock."""

import layer_trace
from stats import percentile


def read(run):
    r = layer_trace.for_run(run)
    w = sorted(r["spans"].get("repro.loader.wait", [])) if r else []
    return 1e3 * percentile(w, 0.5) if w else None
