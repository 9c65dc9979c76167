"""Device ms per window step of the ops under none of the program's scopes:
what the layer buckets leave out, the check on their coverage."""

import layer_trace


def read(run):
    return layer_trace.device_ms_per_step(run, (layer_trace.UNSCOPED,))
