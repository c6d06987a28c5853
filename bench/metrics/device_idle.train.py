"""Share of the traced training window in which no operation ran on the
chip (profiler trace; `trace.Reduction`)."""


def read(run):
    if run.red is None:
        return None
    return 100.0 * (1.0 - run.red.busy_s / run.red.window_s)
