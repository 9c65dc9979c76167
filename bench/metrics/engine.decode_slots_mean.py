"""Mean number of active slots per decode step in the window."""


def read(run):
    d = [s["decode"][1] for s in run.steps if s["decode"]]
    return sum(d) / len(d) if d else None
