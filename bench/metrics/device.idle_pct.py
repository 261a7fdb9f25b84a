"""device.idle_pct: the share of the traced window in which no
operation ran on the TPU (profiler trace: 1 - union of the XLA op
intervals over the window, averaged over the chips)."""


def read(run):
    if run.trace is None or run.trace["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])
