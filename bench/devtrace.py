"""Host spans, and the reduction of a profiler trace to what the per-layer
readers and the ``breakdown`` need.

:class:`Spans` times the benchmark's own phases on the host clock and, in
the same ``with``, writes each as a ``jax.profiler.TraceAnnotation`` named
``bench.<phase>``, so a traced run finds them on the device trace's clock.
:func:`load` turns the profiler's ``.xplane.pb`` into a :class:`TraceView`:
the op events of each device plane, and the benchmark's host spans.  The
interval arithmetic below works on that view alone, so a synthetic view
tests it without a chip.
"""

from __future__ import annotations

import collections
import dataclasses
import re
import time
from contextlib import contextmanager

#: opcodes (or names, for async wrappers) of collective communication
COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|all-to-all|collective-permute|reduce-scatter|"
    r"collective-broadcast|ragged-all-to-all)(-start|-done)?(\.\d+)?$")
#: a TPU trace names each op event by its HLO instruction text:
#: ``%name = type opcode(operands), attributes``
_HLO = re.compile(r"^%(\S+) = .*?\s([a-z][a-z0-9-]*)\(")
_DEVICE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
_OPS_LINE = "XLA Ops"  # every op, nested: a while or conditional holds its body's ops
_ASYNC_LINE = "Async XLA Ops"  # async pairs, each from its start to its done
PREFIX = "bench."


class Spans:
    """The benchmark's phases: ``(name, start_s, end_s)`` on the host clock."""

    def __init__(self):
        self.items: list[tuple[str, float, float]] = []

    @contextmanager
    def __call__(self, name: str):
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(PREFIX + name):
            yield
        self.items.append((name, t0, time.perf_counter()))

    def total(self, *names: str) -> float:
        return sum(t1 - t0 for n, t0, t1 in self.items if n in names)


@dataclasses.dataclass(frozen=True)
class Op:
    name: str  # HLO instruction name, e.g. ``fusion.26``
    start: float  # seconds, on the trace's clock
    end: float
    opcode: str = ""
    text: str = ""  # the instruction as the trace gives it

    @property
    def collective(self) -> bool:
        return bool(COLLECTIVE.match(self.opcode) or COLLECTIVE.match(self.name))

    @property
    def pallas(self) -> bool:
        """A Pallas (Mosaic) kernel: a ``tpu_custom_call``."""
        return 'custom_call_target="tpu_custom_call"' in self.text


@dataclasses.dataclass
class TraceView:
    ops: dict  # device id -> [Op] sorted by start, nested ops included
    spans: list  # [(name, start, end)]: the benchmark's host spans
    window: tuple  # (start, end) of the measured window
    async_ops: dict = dataclasses.field(default_factory=dict)  # device -> [Op]

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]


def load(path: str) -> TraceView:
    """Read a profiler ``.xplane.pb``: device op events and the ``bench.``
    host spans, on one clock, in seconds."""
    from jax.profiler import ProfileData

    ops: dict[int, list[Op]] = {}
    async_ops: dict[int, list[Op]] = {}
    spans = []
    for plane in ProfileData.from_file(path).planes:
        dev = _DEVICE.match(plane.name)
        for line in plane.lines:
            if dev and line.name in (_OPS_LINE, _ASYNC_LINE):
                into = ops if line.name == _OPS_LINE else async_ops
                into[int(dev.group(2))] = sorted(
                    (_op(e) for e in line.events), key=lambda o: (o.start, -o.end))
            elif not dev:
                for e in line.events:
                    if e.name.startswith(PREFIX):
                        t0 = e.start_ns * 1e-9
                        spans.append((e.name[len(PREFIX):], t0,
                                      t0 + e.duration_ns * 1e-9))
    windows = [(t0, t1) for n, t0, t1 in spans if n == "window"]
    if not windows:
        raise ValueError(f"{path}: no bench.window span in the trace")
    return TraceView(ops=ops, spans=spans, window=windows[0], async_ops=async_ops)


def _op(e) -> Op:
    m = _HLO.match(e.name)
    name, opcode = (m.group(1), m.group(2)) if m else (e.name, "")
    t0 = e.start_ns * 1e-9
    return Op(name, t0, t0 + e.duration_ns * 1e-9, opcode, e.name)


# -- interval arithmetic ------------------------------------------------------


def union(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


def length(intervals) -> float:
    return sum(b - a for a, b in intervals)


def overlap(xs, ys) -> float:
    """Length of the intersection of two unions (each sorted, disjoint)."""
    total, j = 0.0, 0
    for a, b in xs:
        while j < len(ys) and ys[j][1] <= a:
            j += 1
        k = j
        while k < len(ys) and ys[k][0] < b:
            total += max(0.0, min(b, ys[k][1]) - max(a, ys[k][0]))
            k += 1
    return total


def gaps(busy, lo: float, hi: float) -> list[tuple[float, float]]:
    """The idle intervals of ``[lo, hi)`` outside the union ``busy``."""
    out, t = [], lo
    for a, b in clip(busy, lo, hi):
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


# -- per-device reductions ------------------------------------------------------


def busy(view: TraceView, dev: int) -> list[tuple[float, float]]:
    """Union of the intervals in which an op ran on ``dev``, in the window."""
    return clip(union((o.start, o.end) for o in view.ops[dev]), *view.window)


def collective(view: TraceView, dev: int) -> list[tuple[float, float]]:
    """Union of the collective intervals of ``dev`` in the window: each
    collective op's own duration, and each async collective in flight,
    from its start op to its done op."""
    spans = [(o.start, o.end) for o in view.ops[dev] if o.collective]
    spans += [(o.start, o.end) for o in view.async_ops.get(dev, []) if o.collective]
    return clip(union(spans), *view.window)


def compute(view: TraceView, dev: int) -> list[tuple[float, float]]:
    """Union of the ops of ``dev`` that are not collectives, in the window.
    A while or conditional op spans its body; only leaf ops count here, so
    that a loop holding a collective does not hide it."""
    return clip(union((o.start, o.end) for o in leaves(view.ops[dev])
                      if not o.collective), *view.window)


def self_times(ops) -> list[tuple[Op, float]]:
    """Each op with its own time: its duration less that of the ops nested
    in it (``ops`` sorted by start, outer before inner)."""
    out, stack = [], []  # stack of [op, self time]
    for o in ops:
        while stack and stack[-1][0].end <= o.start:
            out.append(tuple(stack.pop()))
        if stack:
            stack[-1][1] -= min(o.end, stack[-1][0].end) - o.start
        stack.append([o, o.end - o.start])
    out += [tuple(x) for x in reversed(stack)]
    return out


def leaves(ops) -> list[Op]:
    """The ops with no op nested in them."""
    out = []
    for k, o in enumerate(ops):
        nxt = ops[k + 1] if k + 1 < len(ops) else None
        if nxt is None or nxt.start >= o.end:
            out.append(o)
    return out


def mean_over_devices(view: TraceView, fn) -> float:
    """Mean of ``fn(view, device)`` over the devices (0 with none)."""
    return sum(fn(view, d) for d in view.ops) / max(len(view.ops), 1)


def busy_s(view: TraceView) -> float:
    return mean_over_devices(view, lambda v, d: length(busy(v, d)))


# -- breakdown ------------------------------------------------------------------


def top_ops(view: TraceView, k: int = 10) -> list[list]:
    """The ``k`` ops with the most own device time in the window (nested
    ops' time taken off their loop or conditional), as ``[instruction
    text, seconds per device]``; the same instruction adds up over calls."""
    per = collections.Counter()
    lo, hi = view.window
    for ops in view.ops.values():
        inside = [dataclasses.replace(o, start=max(o.start, lo), end=min(o.end, hi))
                  for o in ops if o.end > lo and o.start < hi]
        for o, own in self_times(inside):
            per[o.text[:160]] += own
    n = max(len(view.ops), 1)
    return [[name, s / n] for name, s in per.most_common(k)]


def idle_gaps(view: TraceView, k: int = 10) -> list[list]:
    """The ``k`` longest idle gaps of the lowest-numbered device in the
    window, each named by the innermost benchmark host span around its
    middle."""
    if not view.ops:
        return []
    dev = min(view.ops)
    out = []
    for a, b in gaps(busy(view, dev), *view.window):
        mid = (a + b) / 2
        around = [(t1 - t0, n) for n, t0, t1 in view.spans
                  if t0 <= mid < t1 and n != "window"]
        out.append([min(around)[1] if around else "no host span", b - a])
    out.sort(key=lambda x: -x[1])
    return out[:k]
