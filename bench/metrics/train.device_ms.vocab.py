"""Device ms per window step of the ops under the program's ``embed`` and
``lm_head`` scopes (token lookup, final norm, the vocabulary-wide head and
the loss, forward and backward), from the traced window's op metadata."""

import layer_trace


def read(run):
    return layer_trace.device_ms_per_step(run, ("embed", "lm_head"))
