"""Device ms per window step of the ops under the program's ``grad_accum``
and ``optimizer`` scopes (f32 gradient accumulation, clipping, the AdamW
update), from the traced window's op metadata."""

import layer_trace


def read(run):
    return layer_trace.device_ms_per_step(run, ("grad_accum", "optimizer"))
