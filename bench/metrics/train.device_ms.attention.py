"""Device ms per window step of the ops under the program's ``attention``
scope (norm, q/k/v, rope, attention, output projection: forward,
recomputed and backward), from the traced window's op metadata."""

import layer_trace


def read(run):
    return layer_trace.device_ms_per_step(run, ("attention",))
