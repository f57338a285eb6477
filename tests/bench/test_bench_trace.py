"""The trace reduction on synthetic traces: busy and idle time, the
collectives and their exposed part, the Pallas kernels' share, the
breakdown, and the per-layer readers that sit on them.  Also one trace
recorded here on the CPU, read back through ``devtrace.load``."""

import glob
from pathlib import Path

import pytest

from bench import devtrace, spec
from bench.cell import Reading

REPO = Path(__file__).resolve().parents[2]
Op = devtrace.Op


def _op(name, start, end, opcode=None, tail=""):
    opcode = opcode or name.split(".")[0]
    return Op(name, start, end, opcode, f"%{name} = s32[8]{{0}} {opcode}(s32[8]{{0}} %p){tail}")


PALLAS = ', custom_call_target="tpu_custom_call"'


def _view():
    # two chips over a 10 s window [0, 10); chip 0 runs a loop holding its
    # body's ops, as a TPU trace nests them
    ops = {
        0: [_op("while.1", 0.0, 5.0), _op("fusion.1", 0.0, 4.0),
            _op("collective-permute-start.2", 4.0, 4.1), _op("fusion.3", 4.5, 5.0),
            _op("collective-permute-done.2", 5.0, 6.0),
            _op("popcount_planes_pallas.1", 6.0, 7.0, "custom-call", PALLAS),
            _op("all-reduce.4", 8.0, 9.0)],
        1: [_op("fusion.1", -1.0, 3.0), _op("all-to-all.7", 3.0, 4.0),
            _op("fusion.9", 3.5, 5.0)],
    }
    async_ops = {0: [_op("collective-permute-start.2", 4.0, 6.0)]}
    spans = [("window", 0.0, 10.0), ("dispatch", 0.0, 0.1), ("wait", 0.1, 9.5),
             ("fetch", 9.5, 10.0)]
    return devtrace.TraceView(ops=ops, spans=spans, window=(0.0, 10.0),
                              async_ops=async_ops)


def test_hlo_text_names_the_op():
    text = ('%pack_pallas.14 = u32[1,8192]{1,0:T(1,128)S(1)} custom-call(u32[2,8192]'
            '{1,0:T(2,128)S(1)} %bitcast.502), custom_call_target="tpu_custom_call"')

    class Event:
        name, start_ns, duration_ns = text, 1e9, 5e8

    op = devtrace._op(Event)
    assert (op.name, op.opcode, op.start, op.end) == ("pack_pallas.14", "custom-call", 1.0, 1.5)
    assert op.pallas and not op.collective
    loop = ("%while.78 = (s32[1,4194304]{1,0:T(1,128)}, pred[]{:T(512)}) "
            "while((s32[1,4194304]{1,0:T(1,128)}) %tuple.5), condition=%c")
    Event.name = loop
    assert devtrace._op(Event).opcode == "while"
    Event.name = "%all-gather-start.3 = (u32[8]{0}, u32[32]{0}) all-gather-start(u32[8]{0} %x)"
    assert devtrace._op(Event).collective


def test_nesting():
    ops = [_op("while.1", 0, 10), _op("fusion.2", 1, 2), _op("cond.3", 3, 9),
           _op("fusion.4", 4, 5), _op("fusion.5", 9.5, 10)]
    own = {o.name: t for o, t in devtrace.self_times(ops)}
    assert own == pytest.approx({"while.1": 2.5, "fusion.2": 1, "cond.3": 5,
                                 "fusion.4": 1, "fusion.5": 0.5})
    assert [o.name for o in devtrace.leaves(ops)] == ["fusion.2", "fusion.4", "fusion.5"]


def test_interval_arithmetic():
    assert devtrace.union([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == [(0, 2.5), (3, 4)]
    assert devtrace.clip([(-1, 1), (2, 3), (9, 12)], 0, 10) == [(0, 1), (2, 3), (9, 10)]
    assert devtrace.gaps([(1, 2), (4, 5)], 0, 6) == [(0, 1), (2, 4), (5, 6)]
    assert devtrace.overlap([(0, 2), (3, 6)], [(1, 4), (5, 7)]) == pytest.approx(3.0)


def test_busy_and_idle():
    v = _view()
    assert devtrace.length(devtrace.busy(v, 0)) == pytest.approx(8.0)  # idle 7-8, 9-10
    assert devtrace.length(devtrace.busy(v, 1)) == pytest.approx(5.0)  # clipped at 0
    assert devtrace.busy_s(v) == pytest.approx(6.5)
    idle = spec.load_reader(REPO, "device_idle_pct")(Reading(v, None, None))
    assert idle == pytest.approx(35.0)


def test_collectives_pair_async_ops_and_find_the_exposed_part():
    v = _view()
    # chip 0: the permute in flight 4-6 plus the all-reduce 8-9; chip 1: 3-4
    assert devtrace.collective(v, 0) == [(4.0, 6.0), (8.0, 9.0)]
    assert devtrace.collective(v, 1) == [(3.0, 4.0)]
    read = lambda name: spec.load_reader(REPO, name)(Reading(v, None, None))  # noqa: E731
    assert read("collective_pct") == pytest.approx(100 * (3.0 + 1.0) / 2 / 10)
    # exposed: chip 0 loses 4.5-5 to fusion.3, chip 1 loses 3.5-4 to fusion.9
    assert read("collective_exposed_pct") == pytest.approx(100 * (2.5 + 0.5) / 2 / 10)


def test_kernel_share_and_silence_without_kernels():
    v = _view()
    share = spec.load_reader(REPO, "kernels_pct")(Reading(v, None, None))
    assert share == pytest.approx(100 * 0.5 / 6.5)
    quiet = devtrace.TraceView(ops={0: [Op("fusion.1", 0.0, 1.0)]}, spans=[],
                               window=(0.0, 2.0))
    for name in ("kernels_pct", "collective_pct", "collective_exposed_pct"):
        assert spec.load_reader(REPO, name)(Reading(quiet, None, None)) is None


def test_breakdown():
    v = _view()
    top = devtrace.top_ops(v, k=3)
    assert top[0] == [_op("fusion.1", 0, 0).text, pytest.approx((4.0 + 3.0) / 2)]
    # the loop keeps only its own time: 5 s less the 4.6 s of its body
    assert [_op("while.1", 0, 0).text, pytest.approx(0.4 / 2)] in devtrace.top_ops(v)
    assert len(top) == 3
    gaps = devtrace.idle_gaps(v)
    assert gaps == [["wait", pytest.approx(1.0)], ["fetch", pytest.approx(1.0)]]


def test_kernel1_reader_reads_host_spans():
    spans = devtrace.Spans()
    spans.items += [("kernel1.csr", 0.0, 2.0), ("kernel1.partition", 2.0, 2.5),
                    ("kernel1.placement", 2.5, 4.0), ("warmup", 4.0, 9.0)]
    assert spec.load_reader(REPO, "kernel1_s")(Reading(None, spans, None)) == 4.0


def test_recorded_trace_reads_back(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    spans = devtrace.Spans()
    jax.profiler.start_trace(str(tmp_path))
    with spans("window"):
        with spans("dispatch"):
            y = f(x)
        with spans("wait"):
            y.block_until_ready()
    jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))[0]
    view = devtrace.load(path)
    names = [n for n, _, _ in view.spans]
    assert {"window", "dispatch", "wait"} <= set(names)
    t0, t1 = view.window
    assert t1 > t0 and all(t0 <= a <= b <= t1 for n, a, b in view.spans if n != "window")
    assert view.ops == {}  # the CPU has no device plane
