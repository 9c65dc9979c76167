"""Tokens served per second: every token the window's steps emitted (first
tokens from prefill and one per active slot per decode step) over the whole
window, on the harness's clock."""


def read(run):
    tokens = sum(len(s["prefills"]) + (s["decode"][1] if s["decode"] else 0)
                 for s in run.steps)
    return tokens / run.window_s
