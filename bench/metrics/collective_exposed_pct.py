"""The part of the collectives' device time in which no other op runs on
that chip, over the measured window, as a mean over the cell's chips
(profiler trace).  A trace with no collective gives nothing."""

from bench import devtrace


def _exposed(view, dev):
    coll = devtrace.collective(view, dev)
    return devtrace.length(coll) - devtrace.overlap(coll, devtrace.compute(view, dev))


def read(reading):
    view = reading.trace
    if not any(devtrace.collective(view, d) for d in view.ops):
        return None
    return 100.0 * devtrace.mean_over_devices(view, _exposed) / view.window_s
