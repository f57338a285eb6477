"""Device time of the collectives (all-to-all, all-gather, all-reduce,
collective-permute; an async pair from its start to its done) over the
measured window, as a mean over the cell's chips (profiler trace).  A
trace with no collective gives nothing."""

from bench import devtrace


def read(reading):
    view = reading.trace
    if not any(devtrace.collective(view, d) for d in view.ops):
        return None
    coll = devtrace.mean_over_devices(
        view, lambda v, d: devtrace.length(devtrace.collective(v, d)))
    return 100.0 * coll / view.window_s
