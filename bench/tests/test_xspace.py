"""The ``tf_op`` decoder and the readers of the sweep's glue and host
hand-off, on traces recorded on a TPU v5e by this harness: a 10 s window of
``jacobi.1chip.2G`` and a short window of ``jacobi.1chip.2M`` with the
sweep's ``jacobi.*`` scopes, and the older 2G trace without them."""

import shutil
import statistics
import tempfile
from pathlib import Path

import pytest

import devtrace
import harness
import xspace
from run import LayerInputs

DATA = Path(__file__).parent / "data"
UNSCOPED = DATA / "jacobi.1chip.2G.xplane.pb"
SCOPED = {"jacobi.1chip.2G": DATA / "jacobi.1chip.2G.scoped.xplane.pb",
          "jacobi.1chip.2M": DATA / "jacobi.1chip.2M.scoped.xplane.pb"}
STEP = ("step.dispatch_us", "step.queue_us", "step.notify_us")


def inputs(cell_name, path, ops=None):
    """What a traced run of ``cell_name`` hands its readers."""
    trace = devtrace.load(path)
    cell = harness.load_cell(cell_name)
    return LayerInputs(
        trace=trace, trace_path=path,
        ops=ops or len(trace.devices[0].modules), window_s=0.0, chips=1,
        peaks=harness.load_peaks("TPU v5 lite"), params=cell.params,
        config=cell.config, extra={"rows": 8, "cols": cell.params["cols"]})


def read_all(x):
    metrics = harness.load_metrics()
    return {name: metrics[name].read(x)
            for name in ("jacobi.glue_ms", "jacobi.kernel_ms") + STEP}


def test_decoder_reads_each_ops_metadata():
    stats = xspace.metadata_stats(UNSCOPED, xspace.DEVICE0)
    (fusion,) = [v for k, v in stats.items()
                 if k.startswith("%pad_slice_fusion =")]
    assert fusion["tf_op"] == "jit(<lambda>)/jit(jacobi_sweep)/slice:"
    assert fusion["hlo_category"] == "loop fusion"
    assert fusion["bytes_accessed"] == 3 << 31
    (kernel,) = [v for k, v in stats.items()
                 if k.startswith("%jacobi_sweep.1 =")]
    assert kernel["tf_op"].endswith("/pallas_call:")
    assert xspace.metadata_stats(UNSCOPED, "/device:TPU:7") == {}


def test_the_trace_without_scopes_has_no_glue():
    got = read_all(inputs("jacobi.1chip.2G", UNSCOPED))
    assert got["jacobi.glue_ms"] is None
    assert 36.5 < got["jacobi.kernel_ms"] < 37.5
    # The host hand-off needs no scope: 207.6, 547.8 and 76.0 us.
    assert 150 < got["step.dispatch_us"] < 300
    assert 300 < got["step.queue_us"] < 800
    assert 30 < got["step.notify_us"] < 150


def test_a_trace_of_another_run_is_not_read(tmp_path, monkeypatch):
    # Another run's trace, without scopes, is the newest under the
    # temporary directory: the readers read the run's own all the same.
    other = tmp_path / "bench-trace-other" / "plugins" / "profile" / "t"
    other.mkdir(parents=True)
    shutil.copy(UNSCOPED, other / "host.xplane.pb")
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    x = inputs("jacobi.1chip.2G", SCOPED["jacobi.1chip.2G"])
    assert 12.5 < read_all(x)["jacobi.glue_ms"] < 13.5
    x.trace_path = None
    assert all(v is None for k, v in read_all(x).items()
               if k != "jacobi.kernel_ms")


def test_host_split_reads_the_host_clock_alone():
    # Each enqueue ends 100 ns into the call; program i runs 500 + i ns;
    # its callbacks start 300 ns after the enqueue ended plus its run, and
    # the wait ends 80 ns later.
    sweeps = [xspace.Sweep(call=10_000 * i, enqueued=10_000 * i + 100,
                           device=500 + i,
                           callbacks=10_000 * i + 400 + 500 + i,
                           waited=10_000 * i + 980 + i)
              for i in range(5)]
    split = xspace.host_split(sweeps)
    assert split == {"dispatch": [100] * 5, "queue": [300] * 5,
                     "notify": [80] * 5}
    for s, d, q, n in zip(sweeps, *split.values()):
        assert d + q + s.device + n == s.waited - s.call


class _Event:
    def __init__(self, start_ns, end_ns):
        self.start_ns, self.end_ns = start_ns, end_ns


@pytest.mark.parametrize("callbacks", [{}, {7: [900, 950]}],
                         ids=["none", "two"])
def test_a_program_without_one_callback_gives_no_sweeps(callbacks):
    calls, waits = [_Event(0, 200)], [_Event(300, 1000)]
    enqueues = [(50, 100, 7)]
    modules = {7: [_Event(5000, 5500)]}
    assert xspace._sweeps(calls, waits, enqueues, modules,
                          {7: [900]}) is not None
    assert xspace._sweeps(calls, waits, enqueues, modules,
                          callbacks) is None


@pytest.mark.parametrize("cell", sorted(SCOPED))
def test_glue_and_kernel_cover_the_busy_time(cell):
    x = inputs(cell, SCOPED[cell])
    got = read_all(x)
    busy_ms = x.trace.devices[0].busy_ns() / x.ops / 1e6
    unscoped = [e for e in x.trace.devices[0].ops
                if "jacobi." not in xspace.load(x).tf_op.get(e.name, "")]
    other_ms = x.trace.devices[0].busy_ns(unscoped) / x.ops / 1e6
    total = got["jacobi.kernel_ms"] + got["jacobi.glue_ms"] + other_ms
    assert total == pytest.approx(busy_ms, rel=0.01)
    if cell == "jacobi.1chip.2G":
        assert other_ms == 0
        assert 12.5 < got["jacobi.glue_ms"] < 13.5
    else:
        # Only the input's copy-start / copy-done carry no scope.
        assert {devtrace.op_kind(e.name) for e in unscoped} == {
            "copy-start", "copy-done"}
        assert 0.0065 < got["jacobi.glue_ms"] < 0.0075


@pytest.mark.parametrize("cell", sorted(SCOPED))
def test_the_hand_off_adds_up_to_the_sweep(cell):
    x = inputs(cell, SCOPED[cell])
    got = read_all(x)
    run = xspace.load(x)
    per_sweep_us = statistics.median(s.waited - s.call
                                     for s in run.sweeps) / 1e3
    busy_us = x.trace.devices[0].busy_ns() / x.ops / 1e3
    parts = sum(got[k] for k in STEP) + busy_us
    assert parts == pytest.approx(per_sweep_us, rel=0.10)
    # Recorded: 202.5, 509.4, 74.4 us at 2G; 150.2, 375.5, 47.2 us at 2M.
    low, high = {"jacobi.1chip.2G": ((150, 300, 30), (300, 800, 150)),
                 "jacobi.1chip.2M": ((120, 250, 20), (200, 500, 100))}[cell]
    for k, lo, hi in zip(STEP, low, high):
        assert lo <= got[k] < hi, k
