"""Host Python of `SwarmHub.tick` per tick: the hub's tick clock
(`prof_tick_s`) less the time inside kernel calls (`prof_kernel_s`), over
the window's ticks."""


def read(run):
    if not run["ticks"]:
        return None
    c = run["counters"]
    return 1000.0 * (c["hub_tick_s"] - c["hub_kernel_s"]) / len(run["ticks"])
