"""Host spans and device scopes of the program (``repro.obs``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import csr
from repro.graphgen import builder, kronecker


@pytest.fixture
def annotations(monkeypatch):
    """Every ``TraceAnnotation`` opened, as ("enter"|"exit", name) events."""
    events = []

    class Recorder:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            events.append(("enter", self.name))

        def __exit__(self, *exc):
            events.append(("exit", self.name))

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorder)
    return events


def test_span_records_and_nests(annotations):
    with obs.span("outer"):
        with obs.span("inner"):
            pass
    assert annotations == [("enter", "repro.outer"), ("enter", "repro.inner"),
                           ("exit", "repro.inner"), ("exit", "repro.outer")]


def test_span_decorates_each_call(annotations):
    @obs.span("twice")
    def f(x):
        return x + 1

    assert f(1) == 2 and f(2) == 3
    assert annotations == [("enter", "repro.twice"), ("exit", "repro.twice")] * 2


def test_setup_spans_name_kernel1_and_placement(annotations):
    """The hybrid containers (kernel 1's last host step) and their
    placement each open a span when they run; CSR build and partition
    open none."""
    from repro.core import distributed_bfs as dbfs
    from repro.launch import runtime

    g = builder.build_csr(kronecker.kronecker_edges(8, seed=1), n=1 << 8)
    bg = csr.partition_2d(g, rows=1, cols=1)
    cfg = dbfs.DistBFSConfig(expand="hybrid")
    blocks = dbfs.shard_blocked(runtime.grid_mesh(1, 1), bg, cfg)
    assert len(blocks) == 5
    assert [n for e, n in annotations if e == "enter"] == ["repro.hybrid_blocked",
                                                          "repro.device_put"]


def test_scoped_names_the_ops_of_each_call():
    @obs.scoped("bfs/update")
    def f(x):
        return jnp.sin(x) * 2

    text = jax.jit(f).lower(np.ones(8, np.float32)).as_text(debug_info=True)
    assert "bfs/update/sin" in text and "bfs/update/mul" in text
