"""Device idle share of the traced window: 1 - (union of the
device-operation intervals) / window, in percent.  Nothing without a
trace or without a device operation."""


def read(run):
    trace = run["trace"]
    if trace is None or trace["window_s"] <= 0 or trace["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
