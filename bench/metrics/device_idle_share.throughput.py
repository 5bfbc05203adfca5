"""Share of the traced serve in which no operation ran on the chip, %:
1 - (union of device operation intervals) / (traced window)."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * run.trace.idle_share
