"""Paged decode attention's share of its roofline: for every call in the
window (one per layer per decode step), the least time the chip needs, the
larger of its operations over peak FLOP/s and its bytes over peak HBM
bandwidth (flops.paged_decode_call, from the step's live sequence lengths),
summed and divided by the kernel's device time in the trace."""

import sys

from flops import paged_decode_call

KERNEL = "paged_decode"


def read(run):
    if not run.trace or not run.peaks:
        return None
    t = sum(v for k, v in run.trace["op_s"].items() if KERNEL in k)
    if t <= 0:
        return None
    pf, pb = run.peaks["bf16_flops_per_s"], run.peaks["hbm_bytes_per_s"]
    L = run.cfg["num_hidden_layers"]
    page = run.sizing.get("page_size", 16)
    need = f_t = b_t = 0.0
    for s in run.steps:
        if s["decode"]:
            f, b = paged_decode_call(run.cfg, s["decode"][2], page)
            need += L * max(f / pf, b / pb)
            f_t += L * f / pf
            b_t += L * b / pb
    print(f"paged_decode: kernel {t:.6f} s, compute bound {f_t:.6f} s, "
          f"memory bound {b_t:.6f} s", file=sys.stderr)
    return 100.0 * need / t
