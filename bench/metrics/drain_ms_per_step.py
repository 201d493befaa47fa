"""Runtime event drain per step: the wall time `SimRuntime.run_batched`
spent draining event bursts in the window (`batched_drain_s`), over the
window's steps (one burst, then one tick)."""


def read(run):
    if not run["ticks"]:
        return None
    return 1000.0 * run["counters"]["drain_s"] / len(run["ticks"])
