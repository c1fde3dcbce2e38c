"""Whole-iteration capture: heterogeneous graphs, one dispatch per step.

Acceptance (ISSUE 7): one captured Jacobi iteration is exactly ONE
dispatch (engine counter AND traced launch counts), numerics are
identical to the eager path, two schedules of the same captured step
digest apart and never cross-serve executables, and calibration never
pools captured-step samples with pure-comm samples.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.comm import (CommConfig, CommSession, ComputeNode, StepCapture,
                        captured_psum)
from repro.comm.calibration import CalibrationFitter
from repro.comm.capture import BufferSpec, lower_step
from repro.comm.telemetry import DispatchSample, StageTimings
from jax import shard_map
from repro.core.halo import jacobi_step, make_captured_jacobi_step


@pytest.fixture()
def sess(dev_mesh):
    return CommSession(mesh=dev_mesh)


# ------------------------- Jacobi acceptance --------------------------------

def test_captured_jacobi_one_dispatch_bitwise_eager(sess):
    """ONE captured Jacobi iteration == ONE dispatch, numerics identical
    to the eager ``jacobi_step`` (bitwise)."""
    n = sess.engine.num_devices
    rows, cols = 8, 12
    u = np.random.default_rng(0).random((n, rows, cols), dtype=np.float32)
    step = make_captured_jacobi_step(sess, rows, cols)
    (out,) = step(u)
    assert sess.stats()["dispatches"] == 1

    eager = shard_map(
        lambda x: jacobi_step(x[0], sess.axis_name)[None],
        mesh=sess.mesh, in_specs=P(sess.axis_name),
        out_specs=P(sess.axis_name), check_vma=False)
    ref = eager(jnp.asarray(u))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    # steady state: still one dispatch per iteration, served by fast path
    (out2,) = step(np.asarray(out))
    assert sess.stats()["dispatches"] == 2
    assert sess.stats()["fastpath"]["hits"] >= 1


def test_captured_jacobi_traced_launch_counts(sess, count_eqns):
    """Traced ppermute + kernel-call count == scheduled num_nodes: the
    compiled step program contains exactly the graph's copy nodes as
    ppermutes and its compute nodes as ``capk_*`` jit calls."""
    eng = sess.engine
    step = make_captured_jacobi_step(sess, 8, 12)
    entry = step.resolve()
    graph = entry.graph
    fn = eng._build_step_fn(entry.program, graph, entry.outputs)
    abstracts = eng._step_abstracts(entry.program)
    ppermutes = count_eqns(fn, abstracts, "ppermute")
    kernels = count_eqns(
        fn, abstracts,
        lambda e: str(e.params.get("name", "")).startswith("capk_"))
    assert ppermutes == graph.num_copy_nodes
    assert kernels == graph.num_compute_nodes
    assert ppermutes + kernels == graph.num_nodes


def test_stats_and_describe_report_breakdown(sess):
    step = make_captured_jacobi_step(sess, 4, 8)
    step.resolve()
    g = sess.stats()["graph"]
    assert g["copy_nodes_compiled"] > 0
    assert g["compute_nodes_compiled"] == 2   # halo_slices + jacobi_sweep
    assert (g["nodes_compiled"]
            == g["copy_nodes_compiled"] + g["compute_nodes_compiled"])
    d = sess.describe(0, 1, 1 << 20, max_paths=2)
    assert d["graph"]["copy_nodes"] == d["graph"]["nodes"]
    assert d["graph"]["compute_nodes"] == 0


# ------------------------- schedules ----------------------------------------

def _multipath_build(cap):
    x = cap.input((1 << 20,), jnp.float32)
    y = cap.kernel(lambda v: v * 2.0, x, name="double")
    (r,) = cap.exchange([(y, 0, 1)], max_paths=2, num_chunks=4)
    return cap.kernel(lambda v: v + 1.0, r, name="inc")


def test_schedules_digest_apart_never_cross_serve(sess):
    """Two schedules of the SAME captured step digest apart: distinct
    plan-cache keys, distinct fast-path entries, no cross-serving."""
    s_rr = sess.capture(_multipath_build, schedule="round_robin")
    s_df = sess.capture(_multipath_build, schedule="depth_first")
    e_rr, e_df = s_rr.resolve(), s_df.resolve()
    assert e_rr.graph.num_copy_nodes > 4   # genuinely multipath
    assert e_rr.digest != e_df.digest
    assert e_rr.key != e_df.key
    assert sess.stats()["cache"]["size"] == 2
    # resolving again serves each schedule its own memoized entry
    assert s_rr.resolve().digest == e_rr.digest
    assert s_df.resolve().digest == e_df.digest


def test_cross_schedule_numerics_and_one_dispatch_each(sess):
    def build(cap):
        x = cap.input((4096,), jnp.float32)
        y = cap.kernel(lambda v: v * 3.0, x, name="triple")
        (r,) = cap.exchange([(y, 0, 1)], num_chunks=2)
        return cap.kernel(lambda v: v - 1.0, r, name="dec")

    n = sess.engine.num_devices
    x = np.random.default_rng(3).random((n, 4096), dtype=np.float32)
    outs = {}
    for sched in ("round_robin", "depth_first", "critical_path"):
        before = sess.stats()["dispatches"]
        (outs[sched],) = sess.capture(build, schedule=sched)(x)
        assert sess.stats()["dispatches"] == before + 1
    expect = x[0] * 3.0 - 1.0           # payload read on src device 0
    for sched, out in outs.items():
        np.testing.assert_array_equal(np.asarray(out[1]), expect)


# ------------------------- captured psum / train ----------------------------

def test_captured_psum_matches_sum(sess):
    n = sess.engine.num_devices
    x = np.arange(n * 16, dtype=np.float32).reshape(n, 16) + 1.0

    def build(cap):
        v = cap.input((16,), jnp.float32)
        return captured_psum(cap, v, n, name="ps")

    (out,) = sess.capture(build)(x)
    assert sess.stats()["dispatches"] == 1
    expect = x.sum(axis=0)
    for d in range(n):
        np.testing.assert_array_equal(np.asarray(out[d]), expect)


def test_captured_train_step_matches_eager_dp(dev_mesh):
    from repro.configs import get_config
    from repro.data import DataConfig, SyntheticDataset
    from repro.optim import OptimConfig
    from repro.training import (TrainStepConfig, init_state,
                                make_captured_dp_train_step,
                                make_dp_train_step)

    cfg = dataclasses.replace(
        get_config("smollm_360m").reduced(), name="mini-cap",
        num_layers=1, d_model=32, num_heads=2, num_kv_heads=2, d_ff=64,
        vocab_size=128)
    opt = OptimConfig(learning_rate=1e-3, warmup_steps=2, total_steps=10)
    ts = TrainStepConfig()
    comm = CommSession(mesh=dev_mesh)
    state_a = init_state(cfg, opt)
    state_b = jax.tree.map(lambda x: x, state_a)
    ds = SyntheticDataset(cfg, DataConfig(seq_len=8, global_batch=8))
    batch = {k: jnp.asarray(v) for k, v in ds.batch_at(0).items()}

    eager = jax.jit(make_dp_train_step(cfg, ts, opt,
                                       CommSession(mesh=dev_mesh)))
    captured = make_captured_dp_train_step(cfg, ts, opt, comm, state_a,
                                           batch)
    state_a, ma = eager(state_a, batch)
    state_b, mb = captured(state_b, batch)
    np.testing.assert_allclose(float(ma["loss"]), float(mb["loss"]),
                               rtol=1e-5)
    for a, b in zip(jax.tree.leaves(state_a["params"]),
                    jax.tree.leaves(state_b["params"])):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=2e-5, rtol=1e-4)
    # grad + (n-1) ring rounds + update, all ONE dispatch
    assert comm.stats()["dispatches"] == 1
    assert comm.stats()["graph"]["compute_nodes_compiled"] >= 3


# ------------------------- calibration isolation ----------------------------

def _sample(compute=(), launch_ns=20_000, execute_ns=100_000):
    routes = (((((0, 1),), 1 << 20, 4),),)
    return DispatchSample(
        routes=routes, nbytes=1 << 20, num_nodes=4, window=1,
        schedule="round_robin",
        stages=StageTimings(launch_ns=launch_ns, execute_ns=execute_ns),
        fastpath_hit=True, compute=compute)


def test_calibration_never_pools_captured_with_pure_comm():
    """Satellite 1: DispatchSample signatures include compute identity,
    and the fitter ignores captured-step samples entirely."""
    pure = _sample()
    captured = _sample(compute=(("jacobi_sweep", 480, 0),))
    assert pure.signature != captured.signature

    from repro.core.topology import Topology
    topo = Topology.full_mesh(4)
    fitter = CalibrationFitter(topo, min_samples=3, warmup=0)
    # only captured-step samples: nothing to fit from
    prof = fitter.fit([captured] * 6)
    assert prof.launch is None
    assert prof.link_bandwidth_gbps == {}
    # mixed: the fit must equal the pure-only fit
    mixed = fitter.fit([pure] * 6 + [captured] * 6)
    pure_only = fitter.fit([pure] * 6)
    assert (mixed.launch is None) == (pure_only.launch is None)
    if mixed.launch is not None:
        assert mixed.launch == pure_only.launch
    assert mixed.link_bandwidth_gbps == pure_only.link_bandwidth_gbps


# ------------------------- capture-surface contracts ------------------------

def test_capture_contracts():
    cap = StepCapture()
    x = cap.input((8,), jnp.float32)
    with pytest.raises(ValueError, match="name"):
        cap.kernel(lambda v: v, x)          # anonymous lambda
    y = cap.kernel(lambda v: v * 2, x, name="k")
    with pytest.raises(ValueError, match="identity"):
        cap.kernel(lambda v: v * 3, x, name="k")   # name reuse
    m = cap.kernel(lambda v: v.reshape(2, 4), x, name="mat")
    with pytest.raises(ValueError, match="1-D"):
        cap.exchange([(m, 0, 1)])
    with pytest.raises(ValueError, match="self-send"):
        cap.exchange([(y, 1, 1)])
    (r,) = cap.exchange([(y, 0, 1)])
    with pytest.raises(ValueError, match="reception"):
        cap.exchange([(r, 1, 2)])           # raw reception re-sent
    # signature is hashable and kernel-name keyed
    hash(cap.signature())


def test_lower_step_heterogeneous_graph(sess):
    cap = StepCapture()
    x = cap.input((1024,), jnp.float32)
    y = cap.kernel(lambda v: v + 1, x, name="inc")
    (r,) = cap.exchange([(y, 0, 1)], num_chunks=2)
    out = cap.kernel(lambda v: v * 2, r, name="dbl")
    graph, plans = lower_step(cap, sess.engine.plan_group_for,
                              sess.topology.name)
    assert graph.num_compute_nodes == 2
    assert graph.num_copy_nodes == sum(
        len(pa.chunk_bounds()) * pa.route.num_hops
        for p in plans for pa in p.paths)
    assert graph.num_nodes == graph.num_copy_nodes + graph.num_compute_nodes
    assert graph.messages   # messages table carried for def-use validation
    # producer kernel precedes first hop; terminal precedes consumer
    kinds = [type(n).__name__ for n in graph.nodes]
    assert kinds[0] == "ComputeNode" and kinds[-1] == "ComputeNode"
    # explicit out= spec path (axis_index kernels)
    cap2 = StepCapture()
    a = cap2.input((4,), jnp.float32)
    b = cap2.kernel(lambda v: v * jax.lax.axis_index("dev"), a,
                    name="scaled", out=BufferSpec((4,), "float32"))
    assert cap2.buffers[b.buf_id].shape == (4,)


def test_compute_node_cost_model():
    from repro.core.pipelining import COMPUTE_GFLOPS, compute_time_s
    measured = ComputeNode("k", 0, (0,), (1,), flops=1000, cost_ns=500)
    declared = ComputeNode("k", 0, (0,), (1,), flops=10 ** 9)
    assert compute_time_s(measured) == 500 / 1e9
    assert compute_time_s(declared) == pytest.approx(
        1.0 / COMPUTE_GFLOPS)


# ------------------------- kernel adopters (§4.4d) --------------------------

def _kernel_op(cap, name):
    """(operands, results, flops, cost_ns) of one recorded kernel op."""
    (rec,) = [op for op in cap.ops
              if op[0] == "kernel" and op[1] == name]
    return rec[2], rec[3], rec[4], rec[5]


def test_captured_ring_allgather_records_and_prices(sess):
    """The ring all-gather adopter records one ComputeNode with the
    declared gather result spec and the telemetry-median ``cost_ns``.

    Capture/model level only: the remote-DMA kernels need jax's typed
    TPU interpret mode to execute (``pltpu.InterpretParams``), which
    this jax lacks — the same gate that skips their eager sweeps in
    ``test_kernels.py`` — so execution coverage lives there.
    """
    from repro.comm.telemetry import TimelineRecorder
    from repro.kernels.ring_allgather.ops import captured_ring_allgather

    rec = TimelineRecorder(enabled=True)
    for ns in (30_000.0, 40_000.0, 50_000.0):
        rec.record_kernel("ring_allgather", ns)
    n = sess.engine.num_devices
    rows, f = 2, 4
    cap = StepCapture()
    x = cap.input((rows, f), jnp.float32)
    out = captured_ring_allgather(cap, x, n, telemetry=rec)
    # gathered (n*rows, f) result spec, wire work (flops 0), measured ns
    assert cap.buffers[out.buf_id].shape == (n * rows, f)
    operands, results, flops, cost_ns = _kernel_op(cap, "ring_allgather")
    assert operands == (x.buf_id,) and results == (out.buf_id,)
    assert flops == 0 and cost_ns == 40_000
    assert callable(cap.kernels["ring_allgather"])


def test_captured_multipath_dma_lowers_into_mixed_graph(sess):
    """The DMA adopter's ComputeNode coexists with ``cap.exchange``
    copies in one lowered heterogeneous graph, and the lane model
    prices its measured duration on the compute lane."""
    from repro.comm import PathPlanner
    from repro.comm.passes import apply_schedule
    from repro.comm.telemetry import TimelineRecorder
    from repro.core.pipelining import compute_time_s

    rec = TimelineRecorder(enabled=True)
    rec.record_kernel("multipath_dma", 25_000.0)
    from repro.kernels.multipath_dma.kernel import dma_granularity
    n = sess.engine.num_devices
    nelems = 2048
    planner = PathPlanner(sess.topology, multipath_threshold=64)
    plan = planner.plan(0, 2, nelems * 4, max_paths=2, num_chunks=2,
                        granularity=dma_granularity(jnp.float32))

    def plan_group_fn(specs, *, max_paths=None, num_chunks=None):
        from repro.comm import TransferRequest
        reqs = [TransferRequest(s, d, ne * 4, granularity=4)
                for (s, d, ne, _) in specs]
        return planner.plan_group(reqs, max_paths=max_paths,
                                  include_host=False,
                                  num_chunks=num_chunks)

    from repro.kernels.multipath_dma.ops import captured_multipath_dma
    cap = StepCapture()
    x = cap.input((nelems,), jnp.float32)
    y = captured_multipath_dma(cap, x, plan, n, telemetry=rec)
    cap.exchange([(y, 0, 1)], num_chunks=2)
    graph, _ = lower_step(cap, plan_group_fn, sess.topology.name)
    assert graph.num_compute_nodes == 1 and graph.num_copy_nodes > 0
    (node,) = [nd for nd in graph.nodes if hasattr(nd, "kernel")]
    assert node.kernel == "multipath_dma" and node.cost_ns == 25_000
    # the stamped measurement is what the lane model charges
    assert compute_time_s(node, sess.topology) == pytest.approx(25e-6)
    # a reorder-only schedule keeps the node multiset (§2.2 contract)
    scheduled, chosen = apply_schedule(graph, "overlap", sess.topology)
    assert chosen == "overlap"
    assert scheduled.num_nodes == graph.num_nodes
    assert scheduled.num_compute_nodes == graph.num_compute_nodes


def test_adopters_stamp_measured_cost_ns():
    """A telemetry recorder with per-kernel measurements prices the
    adopter's ComputeNode by the recorded median (§4.4d close-the-loop);
    without a recorder the declared-FLOPs fallback stands."""
    from repro.comm.telemetry import TimelineRecorder
    from repro.kernels.flash_attention.ops import (attention_flops,
                                                   captured_flash_attention)

    rec = TimelineRecorder(enabled=True)
    for ns in (4_000.0, 5_000.0, 6_000.0):
        rec.record_kernel("flash_attention", ns)
    cap = StepCapture()
    q = cap.input((1, 2, 8, 8), jnp.float32)
    k = cap.input((1, 2, 8, 8), jnp.float32)
    v = cap.input((1, 2, 8, 8), jnp.float32)
    out = captured_flash_attention(cap, q, k, v, telemetry=rec)
    _, _, flops, cost_ns = _kernel_op(cap, "flash_attention")
    assert cost_ns == 5_000                  # the recorded median
    assert flops == attention_flops((1, 2, 8, 8), (1, 2, 8, 8))
    assert cap.buffers[out.buf_id].shape == (1, 2, 8, 8)

    cold = StepCapture()
    q2 = cold.input((1, 2, 8, 8), jnp.float32)
    captured_flash_attention(cold, q2, q2, q2)
    assert _kernel_op(cold, "flash_attention")[3] == 0


# ------------------- overlap acceptance on captured graphs ------------------

def _resolve_graph(sess_like, schedule):
    from repro.core.halo import make_captured_jacobi_step
    step = make_captured_jacobi_step(sess_like, 8, 12, schedule=schedule)
    return step.resolve().graph


def test_overlap_hides_copies_on_captured_jacobi(dev_mesh):
    """ACCEPTANCE: on the captured Jacobi graph the overlap schedule's
    lane makespan is strictly below critical_path's serialized-chain
    makespan — modeled copy time is hidden behind the sweep."""
    from repro.core.pipelining import scheduled_time_s

    ov_sess = CommSession(CommConfig(multipath_threshold=64), mesh=dev_mesh)
    cp_sess = CommSession(CommConfig(multipath_threshold=64), mesh=dev_mesh)
    ov = _resolve_graph(ov_sess, "overlap")
    cp = _resolve_graph(cp_sess, "critical_path")
    lane = scheduled_time_s(ov, ov_sess.topology, mode="lanes")
    serialized = scheduled_time_s(cp, cp_sess.topology, mode="serialized")
    assert lane < serialized                 # strictly hides copy time


def test_overlap_hides_copies_on_captured_dp_train_graph():
    """ACCEPTANCE: same strict inequality on the captured DP-train mixed
    graph (grad → multipath all-reduce → update) in the launch-bound
    regime, priced model-only like the CI overlap gate."""
    from repro.comm import PathPlanner, TransferRequest
    from repro.comm.capture import captured_psum
    from repro.comm.passes import apply_schedule
    from repro.core import Topology
    from repro.core.pipelining import scheduled_time_s

    ndev, nelems = 4, 1 << 10
    topo = Topology.full_mesh(ndev, with_host=False)
    planner = PathPlanner(topo, multipath_threshold=256)

    def plan_group_fn(specs, *, max_paths=None, num_chunks=None):
        reqs = [TransferRequest(s, d, ne * 4, granularity=4)
                for (s, d, ne, _) in specs]
        return planner.plan_group(reqs, max_paths=max_paths,
                                  include_host=False, num_chunks=num_chunks)

    cap = StepCapture()
    x = cap.input((nelems,), jnp.float32)
    g = cap.kernel(lambda v: v * 2.0, x, name="grad", flops=6 * nelems)
    tot = captured_psum(cap, g, ndev, num_chunks=2, name="gradsum")
    cap.kernel(lambda t, v: t / ndev + v, tot, x, name="update",
               flops=10 * nelems)
    graph, _ = lower_step(cap, plan_group_fn, topo.name)

    ov, _ = apply_schedule(graph, "overlap", topo)
    cp, _ = apply_schedule(graph, "critical_path", topo)
    lane = scheduled_time_s(ov, topo, mode="lanes")
    serialized = scheduled_time_s(cp, topo, mode="serialized")
    assert lane < serialized


# ------------------------- captured decode step -----------------------------

def test_captured_decode_step_overlaps_kv_migration(sess):
    """Flagship overlap adopter: ONE dispatch, attention numerics match
    the reference, the KV chunk lands on dst, and the lane model shows
    copy time hidden behind the attention kernel."""
    from repro.core.pipelining import hidden_copy_time_s
    from repro.kernels.flash_attention.ref import attention_ref
    from repro.serving.engine import make_captured_decode_step

    n = sess.engine.num_devices
    batch, heads, kv_len, head_dim, kv_chunk = 1, 2, 16, 8, 4096
    step = make_captured_decode_step(
        sess, batch=batch, heads=heads, kv_len=kv_len, head_dim=head_dim,
        kv_chunk=kv_chunk, src=0, dst=2, schedule="overlap")
    rng = np.random.default_rng(3)
    shp = (n, batch, heads, kv_len, head_dim)
    q = rng.random(shp).astype(np.float32)
    k = rng.random(shp).astype(np.float32)
    v = rng.random(shp).astype(np.float32)
    kv = rng.random((n, kv_chunk)).astype(np.float32)
    attn, new_kv = step(q, k, v, kv)
    assert sess.stats()["dispatches"] == 1

    for d in range(n):                       # per-device attention
        ref = attention_ref(jnp.asarray(q[d]), jnp.asarray(k[d]),
                            jnp.asarray(v[d]), causal=True)
        np.testing.assert_allclose(np.asarray(attn)[d], np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
    expect = kv.copy()
    expect[2] = kv[0]                        # the migrated chunk
    np.testing.assert_allclose(np.asarray(new_kv), expect, rtol=1e-6)

    # the lane model prices the migration copies behind attention
    graph = step.resolve().graph
    assert hidden_copy_time_s(graph, sess.topology) > 0.0
