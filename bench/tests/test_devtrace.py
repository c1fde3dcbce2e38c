"""The trace reduction, on a small trace recorded on a TPU v5e (a traced
window of 195 sweeps of ``jacobi.1chip.2G``) and on made-up traces of
sends."""

import json
from pathlib import Path
from types import SimpleNamespace

import pytest

import devtrace
import harness
import xspace
from run import LayerInputs

DATA = Path(__file__).parent / "data"
TRACE = DATA / "jacobi.1chip.2G.xplane.pb"
SCOPED_2G = DATA / "jacobi.1chip.2G.scoped.xplane.pb"
SWEEPS = 195
SPEC = harness.load_spec()


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
        cell, harness.load_metrics(), SPEC)}
    x = LayerInputs(trace=trace, trace_path=None, ops=SWEEPS,
                    window_s=10.05, chips=1,
                    peaks=harness.load_peaks("TPU v5 lite"),
                    params=cell.params, config=cell.config,
                    extra={"rows": 8, "cols": 1 << 26})
    kernel = metrics["jacobi.kernel_ms"].read(x)
    assert 36.5 < kernel < 37.5
    share = metrics["jacobi_sweep_roofline"].read(x)
    assert 10 < share < 11
    # No transfer program and no sends in this trace: those readers find
    # nothing and say so.
    for name in ("send.program_ms", "send.outside_ms",
                 "send.permute_overlap", "send.host_us", "send.device_us",
                 "send_ici_roofline"):
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
    x = LayerInputs(trace=devtrace.Trace([dev], {}), trace_path=None, ops=2,
                    window_s=1.0, chips=1, peaks={}, params={}, config={})
    metrics = harness.load_metrics()
    # (0, 50) and (1, 70): 119 ns in flight over a union of 70 ns.
    assert metrics["send.permute_overlap"].read(x) == pytest.approx(119 / 70)
    assert metrics["send.program_ms"].read(x) == pytest.approx(100 / 2 / 1e6)
    assert metrics["send.outside_ms"].read(x) == pytest.approx(50 / 2 / 1e6)
    # Ops busy over (0, 2), (40, 50), (60, 70) and (120, 150): 52 ns over
    # 2 sends.
    assert metrics["send.device_us"].read(x) == pytest.approx(52 / 2 / 1e3)


def test_send_ici_roofline_on_a_made_up_trace():
    # Two chips; the busier is busy 6 ms in all over two sends.
    busy = [ev("%fusion.1 = f32[4]{0} fusion()", 0, 4e6),
            ev("%fusion.2 = f32[4]{0} fusion()", 5e6, 7e6)]
    quiet = [ev("%fusion.3 = f32[4]{0} fusion()", 0, 1e6)]
    devs = [devtrace.Device(0, quiet, []), devtrace.Device(1, busy, [])]
    x = LayerInputs(trace=devtrace.Trace(devs, {}), trace_path=None, ops=2,
                    window_s=1.0, chips=2, peaks={"ici_Gbps": 1600},
                    params={}, config={}, extra={"nbytes": 10**8})
    roofline = harness.load_metrics()["send_ici_roofline"]
    # 1e8 B at 200e9 B/s is 0.5 ms; the busier chip, 3 ms a send.
    assert roofline.read(x) == pytest.approx(100 * 0.5 / 3)
    x.trace = devtrace.Trace([devtrace.Device(0, [], []),
                              devtrace.Device(1, [], [])], {})
    assert roofline.read(x) is None
    x.trace, x.extra = devtrace.Trace(devs, {}), {}
    assert roofline.read(x) is None


# -- a made-up traced run of two sends ---------------------------------------

def _text_proto(planes) -> str:
    """An ``XSpace`` in text form: ``planes`` maps a plane's name to its
    lines, each a list of ``(name, start_ns, end_ns, stats)``; an event's
    ``tf_op`` stat goes on its metadata, as the profiler puts it there."""
    out = []
    for pid, (plane, lines) in enumerate(planes.items(), 1):
        names, stats, body = {}, {}, []
        for lid, (line, events) in enumerate(lines.items(), 1):
            evs = []
            for name, start, end, st in events:
                meta = names.setdefault(name, (len(names) + 1, st.get(
                    "tf_op")))
                fields = "".join(
                    f" stats {{ metadata_id: "
                    f"{stats.setdefault(k, len(stats) + 1)} "
                    + (f"str_value: {json.dumps(v)}" if isinstance(v, str)
                       else f"int64_value: {v}") + " }"
                    for k, v in st.items() if k != "tf_op")
                evs.append(f"events {{ metadata_id: {meta[0]} offset_ps: "
                           f"{int(start * 1000)} duration_ps: "
                           f"{int((end - start) * 1000)}{fields} }}")
            body.append(f'lines {{ id: {lid} name: {json.dumps(line)} '
                        f'timestamp_ns: 0 {" ".join(evs)} }}')
        tf_op_id = stats.setdefault("tf_op", len(stats) + 1)
        for name, (mid, tf_op) in names.items():
            st = (f" stats {{ metadata_id: {tf_op_id} str_value: "
                  f"{json.dumps(tf_op)} }}" if tf_op else "")
            body.append(f"event_metadata {{ key: {mid} value {{ id: {mid} "
                        f"name: {json.dumps(name)}{st} }} }}")
        for name, sid in stats.items():
            body.append(f"stat_metadata {{ key: {sid} value {{ id: {sid} "
                        f"name: {json.dumps(name)} }} }}")
        out.append(f"planes {{ id: {pid} name: {json.dumps(plane)} "
                   + " ".join(body) + " }")
    return "\n".join(out)


def _permute(kind, n, operand):
    return (f"%collective-permute-{kind}.{n} = f32[4]{{0}} "
            f"collective-permute-{kind}({operand})")


def _send_planes(sends=2, chips=4):
    """Each send, as ``run.py`` and the engine run it: the host's call
    enqueues three programs on every chip (staging, the transfer program,
    the extraction), 10 us apart; each runs 2, 6 and 1 us."""
    devices, enqueues, callbacks, py = {}, [], [], []
    progs = (("jit_comm_stage(1)", 2, [("%fusion.1 = f32[4]{0} fusion()",
                                        "jit(comm_stage)/scatter")]),
             ("jit_local_body(2)", 6, [
                 (_permute("start", 1, "f32[4]{0} %x"), "jit(local_body)"),
                 (_permute("done", 1, "f32[4]{0} %collective-permute-start.1"),
                  "jit(local_body)")]),
             ("jit_comm_extract(3)", 1, [("%all-reduce.1 = f32[4]{0} "
                                          "all-reduce()", "jit(comm_extract)")]))
    for c in range(chips):
        modules, ops = [], []
        for i in range(sends):
            for j, (mod, us, body) in enumerate(progs):
                start = 100_000 * i + 10_000 * j + 5_000
                run_id = 10 * i + j
                modules.append((mod, start, start + us * 1000,
                                {"run_id": run_id}))
                step = us * 1000 // len(body)
                for k, (op, tf_op) in enumerate(body):
                    ops.append((op, start + k * step, start + (k + 1) * step,
                                {"tf_op": tf_op}))
                if c == 0:
                    enqueues.append(("DoEnqueueProgram", start - 4_000,
                                     start - 3_000, {"device_ordinal": 0,
                                                     "run_id": run_id}))
                    callbacks.append(("CompleteCallbacks", start + 8_000,
                                      start + 8_500, {"run_id": run_id}))
        devices[f"/device:TPU:{c}"] = {"XLA Modules": modules,
                                       "XLA Ops": ops}
    py.append(("bench.window", 0, 100_000 * sends, {}))
    for i in range(sends):
        py.append(("bench.call", 100_000 * i, 100_000 * i + 30_000, {}))
        py.append(("bench.wait", 100_000 * i + 30_000,
                   100_000 * i + 40_000, {}))
    return {**devices, "/host:CPU": {"python": py,
                                     "runtime": enqueues + callbacks}}


@pytest.fixture(scope="module")
def send_trace(tmp_path_factory):
    from jax.profiler import ProfileData
    path = tmp_path_factory.mktemp("send") / "host.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(
        _text_proto(_send_planes())))
    return path


def test_made_up_send_trace(send_trace):
    tr = devtrace.load(send_trace)
    assert [d.id for d in tr.devices] == [0, 1, 2, 3]
    assert [len(d.modules) for d in tr.devices] == [6] * 4
    # Per send on each chip: 2 + 6 + 1 us of ops.
    assert tr.devices[3].busy_ns() == 2 * 9_000


def _step_ms_readers():
    """Every reader of the sweeps' ``step_ms`` and the sends' ``send_ms``
    (and ``step_ms``, which the small sends are to report)."""
    return {n: m for n, m in harness.load_metrics().items()
            if m.moves in ("step_ms", "send_ms")}


@pytest.mark.parametrize("cell", ["jacobi.1chip.2G", "p2p.diag.512M"])
def test_step_ms_readers_report_only_on_their_own_cells(cell, send_trace):
    """Every reader that moves ``step_ms`` or ``send_ms`` runs on a traced
    Jacobi sweep and on a traced send. Each finds a number only in its own kind of run: the
    sweep's kernel, glue, roofline and hand-off readers on the sweep (the
    send has no custom call, no grid, no glue scope, and three programs a
    call), the send readers on the send."""
    if cell == "p2p.diag.512M":
        path, ops, chips = send_trace, 2, 4
        stages = SimpleNamespace(staging_ns=30_000, launch_ns=5_000)
        extra = {"nbytes": 1 << 29,
                 "telemetry": [SimpleNamespace(stages=stages)] * 2}
        own = {n for n in _step_ms_readers() if n.startswith("send")}
    else:
        path, ops, chips = SCOPED_2G, None, 1
        extra = {"rows": 8, "cols": 1 << 26}
        own = {n for n in _step_ms_readers() if n.startswith(("jacobi",
                                                              "step."))}
    tr = devtrace.load(path)
    c = harness.load_cell(cell)
    x = LayerInputs(trace=tr, trace_path=path,
                    ops=ops or len(tr.devices[0].modules), window_s=1.0,
                    chips=chips, peaks=harness.load_peaks("TPU v5 lite"),
                    params=c.params, config=c.config, extra=extra)
    got = {n: m.read(x) for n, m in _step_ms_readers().items()}
    assert {n for n, v in got.items() if v is not None} == own
    listed = {m.name for m in harness.metrics_for(
        c, harness.load_metrics(), SPEC)}
    assert listed <= own
    if cell == "p2p.diag.512M":
        assert xspace.load(x).sweeps is None    # three programs a call
        assert got["send.program_ms"] == pytest.approx(6e3 / 1e6)
        assert got["send.outside_ms"] == pytest.approx(3e3 / 1e6)
        assert got["send.permute_overlap"] == pytest.approx(1.0)
        assert got["send.device_us"] == pytest.approx(9.0)
        assert got["send.host_us"] == pytest.approx(35.0)
        # 2^29 B at 200 GB/s over 9 us a send.
        assert got["send_ici_roofline"] == pytest.approx(
            100 * (1 << 29) / 200e9 / 9e-6)


def test_a_chip_whose_record_stops_early_is_cut_short(tmp_path):
    """The profiler may drop a chip's events once its buffers fill: that
    chip's record stops before the window's end, and it is left out."""
    from jax.profiler import ProfileData
    planes = _send_planes(sends=10)
    dev0 = planes["/device:TPU:0"]
    for line in dev0:
        dev0[line] = [e for e in dev0[line] if e[1] < 200_000]
    path = tmp_path / "host.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(
        _text_proto(planes)))
    tr = devtrace.load(path)
    short = devtrace.cut_short(tr, "bench.window", "bench.call")
    assert [d.id for d in short] == [0]


def test_permute_overlap_is_read_on_the_busiest_chip():
    def perm(kind, n, s, e, operand=""):
        return ev(f"%collective-permute-{kind}.{n} = f32[4]{{0}} "
                  f"collective-permute-{kind}({operand})", s, e)

    def chip(i, second_start, end):
        ops = [perm("start", 1, 0, 1, "f32[4]{0} %x"),
               perm("start", 2, second_start, second_start + 1,
                    "f32[4]{0} %y"),
               perm("done", 1, 40, 50, "f32[4]{0} %collective-permute-start.1"),
               perm("done", 2, 60, 70, "f32[4]{0} %collective-permute-start.2")]
        return devtrace.Device(i, ops, [ev("jit_local_body(7)", 0, end)])
    # Chip 0 ran its permutes one after another in a shorter record; chip 1
    # spent longer in the transfer program, with both permutes in flight.
    devs = [chip(0, 50, 80), chip(1, 0, 100)]
    x = LayerInputs(trace=devtrace.Trace(devs, {}), trace_path=None, ops=1,
                    window_s=1.0, chips=2, peaks={}, params={}, config={})
    assert harness.load_metrics()["send.permute_overlap"].read(x) == (
        pytest.approx((50 + 70) / 70))


def test_no_chip_is_cut_short_in_a_whole_record(send_trace, trace):
    for tr in (devtrace.load(send_trace), trace):
        assert devtrace.cut_short(tr, "bench.window", "bench.call") == []
