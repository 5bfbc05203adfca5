"""Mean device time of one run of the paged decode step, ms (the
``decode_step`` program's events in the trace); it sets the floor under
the time per output token."""


def read(run):
    if run.trace is None or not run.trace.module_n.get("decode_step"):
        return None
    return 1e3 * run.trace.module_s["decode_step"] \
        / run.trace.module_n["decode_step"]
