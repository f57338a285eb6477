"""The measured window and the ``teps`` arithmetic, on a fake clock."""

import numpy as np
import pytest

from bench import cell, devtrace


class Clock:
    def __init__(self):
        self.now = 100.0

    def __call__(self):
        return self.now


def test_search_running_at_the_deadline_finishes_and_counts():
    clock = Clock()

    def call(root):
        clock.now += 4.0  # each search takes 4 s
        return root

    w = cell.drive(call, lambda out: None, iter([10, 11, 12, 13, 14]), 10.0,
                   devtrace.Spans(), clock=clock)
    # searches end at 4, 8 and 12 s: the third runs across the 10 s deadline
    assert w.batches == [10, 11, 12] and w.outs == [10, 11, 12]
    assert w.seconds == pytest.approx(12.0)
    # traversed tuples of all three over the whole 12 s
    assert cell.teps([6e6, 6e6, 12e6], w.seconds) == pytest.approx(2.0)


def test_window_ends_at_a_completion_exactly_on_the_deadline():
    clock = Clock()

    def call(root):
        clock.now += 5.0
        return root

    w = cell.drive(call, lambda out: None, iter(range(9)), 10.0, devtrace.Spans(),
                   clock=clock)
    assert w.batches == [0, 1] and w.seconds == pytest.approx(10.0)


def test_schedule_cycles_through_the_roots():
    roots = np.array([5, 6, 7], np.int32)
    one = cell.schedule(roots, 1)
    assert [next(one) for _ in range(5)] == [5, 6, 7, 5, 6]
    two = cell.schedule(roots, 2)
    assert [next(two).tolist() for _ in range(3)] == [[5, 6], [7, 5], [6, 7]]


def test_spans_time_each_phase():
    spans = devtrace.Spans()
    with spans("kernel1.csr"):
        pass
    with spans("kernel1.csr"):
        pass
    assert [n for n, _, _ in spans.items] == ["kernel1.csr"] * 2
    assert spans.total("kernel1.csr") >= 0 and spans.total("other") == 0
