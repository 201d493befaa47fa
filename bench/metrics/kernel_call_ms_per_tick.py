"""Kernel wrappers per tick: wall time inside the `swarm_kernels` calls
of the window (`prof_kernel_s`: dispatch, host-device copies and the wait
for the result), over the window's ticks."""


def read(run):
    if not run["ticks"]:
        return None
    return 1000.0 * run["counters"]["hub_kernel_s"] / len(run["ticks"])
