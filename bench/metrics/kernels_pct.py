"""Device time of the program's Pallas kernels (``kernels/popcount``,
``kernels/bitpack``: each a ``tpu_custom_call`` named after its kernel,
such as ``popcount_planes_pallas.6``) over device busy time, as a mean
over the cell's chips (profiler trace).  A trace without one gives
nothing."""

from bench import devtrace


def _pallas(view, dev):
    return devtrace.clip(devtrace.union(
        (o.start, o.end) for o in view.ops[dev] if o.pallas), *view.window)


def read(reading):
    view = reading.trace
    if not any(_pallas(view, d) for d in view.ops):
        return None
    kernel = devtrace.mean_over_devices(view, lambda v, d: devtrace.length(_pallas(v, d)))
    return 100.0 * kernel / devtrace.busy_s(view)
