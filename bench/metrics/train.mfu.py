"""Model FLOP/s utilization of training: forward and backward operations per
token (three forwards, causal attention over the row, no recomputation
counted) times tokens per second, over the chip's peak."""

from flops import train_flops_per_token


def read(run):
    if not run.peaks or not run.steps:
        return None
    per_tok = train_flops_per_token(run.family, run.cfg, run.cfg["train"]["seq_len"])
    tokens = sum(s["tokens"] for s in run.steps)
    return 100.0 * per_tok * tokens / run.window_s / run.peaks["bf16_flops_per_s"]
