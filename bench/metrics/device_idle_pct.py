"""Share of the measured window in which no op ran on the device: one
minus the union of the device's op intervals over the window, as a mean
over the cell's chips (profiler trace)."""

from bench import devtrace


def read(reading):
    view = reading.trace
    if not view.ops:
        return None
    return 100.0 * (1.0 - devtrace.busy_s(view) / view.window_s)
