"""Share of the traced serve window in which no operation ran on the device
(1 - union of device operation intervals / window)."""


def read(run):
    if not run.trace:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])
