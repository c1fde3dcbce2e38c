"""The trace reduction, on a small trace recorded on a TPU v5e: a traced
window of 195 sweeps of ``jacobi.1chip.2G``."""

from pathlib import Path

import pytest

import devtrace
import harness
from run import LayerInputs

TRACE = Path(__file__).parent / "data" / "jacobi.1chip.2G.xplane.pb"
SWEEPS = 195


@pytest.fixture(scope="module")
def trace():
    return devtrace.load(TRACE)


def ev(name, s, e):
    return devtrace.Event(name, s, e)


def test_interval_arithmetic():
    spans = devtrace.union([ev("a", 0, 10), ev("b", 5, 20), ev("c", 30, 40)])
    assert spans == [(0, 20), (30, 40)]
    assert devtrace.length(spans) == 30
    assert devtrace.gaps(spans) == [(20, 30)]


def test_op_names_and_kinds():
    text = ('%collective-permute-done.3 = f32[1,1,4096]{2,1,0} '
            'collective-permute-done(f32[1,1,4096]{2,1,0} '
            '%collective-permute-start.3)')
    assert devtrace.op_name(text) == "collective-permute-done.3"
    assert devtrace.op_kind(text) == "collective-permute-done"
    tup = ('%f = (f32[8]{0:T(8)S(1)}, f32[8]{0}) fusion(f32[8]{0} %x), '
           'kind=kLoop')
    assert devtrace.op_kind(tup) == "fusion"
    assert devtrace.base_name("jit_stage(12)") == "jit_stage"


def test_recorded_trace(trace):
    (dev,) = trace.devices
    assert len(dev.modules) == SWEEPS
    kinds = {devtrace.op_kind(e.name) for e in dev.ops}
    assert kinds == {"custom-call", "fusion"}
    targets = {devtrace.custom_call_target(e.name) for e in dev.ops}
    assert targets == {"tpu_custom_call", None}
    assert 9.7e9 < dev.busy_ns() < 9.8e9
    # The device waits only while the host waits on the previous sweep.
    gaps = dict(devtrace.idle_gaps(trace, "bench.window"))
    assert set(gaps) == {"bench.wait"}
    assert 0.2 < gaps["bench.wait"] < 0.4
    (top, secs), *_ = devtrace.top_ops(trace, 1)
    assert top == "jacobi_sweep.1" and 7.1 < secs < 7.3


def test_jacobi_readers_on_the_recorded_trace(trace):
    cell = harness.load_cell("jacobi.1chip.2G")
    metrics = {m.name: m for m in harness.metrics_for(
        cell, harness.load_metrics())}
    x = LayerInputs(trace=trace, ops=SWEEPS, window_s=10.05, chips=1,
                    peaks=harness.load_peaks("TPU v5 lite"),
                    params=cell.params, config=cell.config,
                    extra={"rows": 8, "cols": 1 << 26})
    kernel = metrics["jacobi.kernel_ms"].read(x)
    assert 36.5 < kernel < 37.5
    share = metrics["jacobi_sweep_roofline"].read(x)
    assert 10 < share < 11
    # No transfer program and no sends in this trace: those readers find
    # nothing and say so.
    for name in ("bw.program_ms", "bw.outside_ms", "bw.permute_overlap",
                 "lat.host_us"):
        assert harness.load_metrics()[name].read(x) is None


def test_transfer_program_readers_on_a_made_up_trace():
    def perm(kind, n, s, e, operand=""):
        return ev(f"%collective-permute-{kind}.{n} = f32[4]{{0}} "
                  f"collective-permute-{kind}({operand})", s, e)
    ops = [perm("start", 1, 0, 1, "f32[4]{0} %x"),
           perm("start", 2, 1, 2, "f32[4]{0} %y"),
           perm("done", 1, 40, 50, "f32[4]{0} %collective-permute-start.1"),
           perm("done", 2, 60, 70, "f32[4]{0} %collective-permute-start.2"),
           ev("%fusion.1 = f32[4]{0} fusion(f32[4]{0} %z)", 120, 150)]
    dev = devtrace.Device(0, ops, [ev("jit_local_body(7)", 0, 100),
                                   ev("jit_stage(3)", 110, 160)])
    x = LayerInputs(trace=devtrace.Trace([dev], {}), ops=2, window_s=1.0,
                    chips=1, peaks={}, params={}, config={})
    metrics = harness.load_metrics()
    # (0, 50) and (1, 70): 119 ns in flight over a union of 70 ns.
    assert metrics["bw.permute_overlap"].read(x) == pytest.approx(119 / 70)
    assert metrics["bw.program_ms"].read(x) == pytest.approx(100 / 2 / 1e6)
    assert metrics["bw.outside_ms"].read(x) == pytest.approx(50 / 2 / 1e6)
