"""Training tokens per second: the tokens of every step dispatched in the
window over the time until the last of them finished."""


def read(run):
    return sum(s["tokens"] for s in run.steps) / run.window_s
