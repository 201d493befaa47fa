"""Device kernels per tick: the union of the device-operation intervals
in the traced window, over the window's ticks.  Nothing without a trace
or without a device operation."""


def read(run):
    trace = run["trace"]
    if trace is None or not run["ticks"] or trace["busy_s"] <= 0:
        return None
    return 1000.0 * trace["busy_s"] / len(run["ticks"])
