"""Device: share of the traced window in which no operation ran on the
chip, 1 minus the union of the device's operation intervals, from the
profiler trace. Moves ``itl_p50_ms``."""


def read(run):
    if run.events is None:
        return None
    return 100.0 * (1.0 - run.busy_s / run.window_s)
