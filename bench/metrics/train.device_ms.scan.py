"""Device ms per window step of the two scans' own work: ops under the
program's ``layers`` or ``microbatches`` scope and under no deeper one
(the stacked carries' saves and restores, loop bookkeeping), from the
traced window's op metadata."""

import layer_trace


def read(run):
    return layer_trace.device_ms_per_step(run, ("layers", "microbatches"))
