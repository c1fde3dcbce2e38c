#!/usr/bin/env python3
"""Run the multipath transfer engine's main path once on a TPU and check it.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # one v5e:2x2 host, four chips

One chip: a ``CommSession`` on the discovered device; the Jacobi solver of
the paper's §5.4 through ``repro.core.halo.jacobi_step(multipath=True,
use_kernel=True)`` with the compiled Pallas stencil, checked against
``kernels/jacobi/ref.py``; and the host-staged rung of the degradation
ladder at 512 MiB, checked bit-exact against its source.

Four chips: ``session.send`` of 512 MiB f32 over a neighbour pair (0->1)
and a diagonal pair (0->3), ``session.exchange`` 0<->3, each checked
bit-exact against a plain single-pair ``lax.ppermute``; and the captured
Jacobi step, checked bitwise against the eager ``jacobi_step``.

Every phase, plan, placement, check and wall time goes to stdout; the wall
times are smoke timings, not benchmark results. The last line is one JSON
object, printed only when every check passed. With no TPU the script exits
non-zero and never falls back to the CPU. Everything runs in this one
process, which holds the chips.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

MiB = 1 << 20
#: f32 elements in the 512 MiB messages (the paper's largest OMB size).
MSG_ELEMS = 512 * MiB // 4
#: The paper's Jacobi domain is 8 rows tall (§5.4).
JACOBI_ROWS = 8
#: One-chip Jacobi grid: 8 x 2^25 f32 = 1 GiB, 1/16 of a v5e chip's HBM.
JACOBI_COLS = 1 << 25
JACOBI_SWEEPS = 4
#: Columns per chip of the four-chip captured Jacobi step (8 x 2^24 f32 =
#: 512 MiB per chip).
CAPTURED_COLS = 1 << 24
CAPTURED_ITERS = 3


class SmokeFailure(Exception):
    """A check of the smoke run did not hold."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)
    print(f"  check ok: {what}", flush=True)


def log(msg: str) -> None:
    print(msg, flush=True)


class Phase:
    """Names a phase on stdout and prints its wall time on exit."""

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        log(f"== phase {self.name}")
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        log(f"   smoke wall time {self.name}: "
            f"{time.perf_counter() - self.t0:.3f} s (not a benchmark)")
        return False


def bits_equal(a, b) -> bool:
    """Bit-exact equality of two arrays, compared on the host."""
    import numpy as np
    a, b = np.asarray(a), np.asarray(b)
    if a.shape != b.shape or a.dtype != b.dtype:
        return False
    view = np.dtype(f"u{a.dtype.itemsize}")
    return bool(np.array_equal(a.view(view), b.view(view)))


def describe_plan(plan) -> str:
    paths = "; ".join(
        f"{pa.route.kind} {list(pa.route.directional_links())} "
        f"{pa.nbytes} B in {pa.num_chunks} chunks" for pa in plan.paths)
    return (f"{plan.src}->{plan.dst}: {plan.num_paths} paths, "
            f"{plan.num_nodes} copy nodes [{paths}]")


def placement(x) -> str:
    return (f"sharding={x.sharding} devices="
            f"{sorted(d.id for d in x.devices())}")


def print_memory(devices) -> None:
    for d in devices:
        stats = d.memory_stats() or {}
        log(f"   device {d.id} peak_bytes_in_use="
            f"{stats.get('peak_bytes_in_use', 'not reported')} "
            f"bytes_limit={stats.get('bytes_limit', 'not reported')}")


def random_f32(seed: int, shape, sharding=None):
    """Seeded N(0, 1) f32 data made on the device, in bulk."""
    import jax
    import jax.numpy as jnp
    fn = jax.jit(lambda k: jax.random.normal(k, shape, jnp.float32),
                 out_shardings=sharding)
    return fn(jax.random.key(seed))


# -- one chip -----------------------------------------------------------------

def jacobi_one_chip(session, rows: int, cols: int, sweeps: int,
                    require_compiled_kernel: bool) -> None:
    """Jacobi sweeps through ``jacobi_step`` in a ``shard_map`` over the
    session's one-device mesh, against the pure-jnp reference."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.halo import jacobi_step
    from repro.kernels.jacobi.ref import jacobi_sweep_ref

    ax = session.axis_name
    mesh = session.mesh
    step = jax.shard_map(
        lambda x: jacobi_step(x[0], ax, multipath=True,
                              use_kernel=True)[None],
        mesh=mesh, in_specs=P(ax), out_specs=P(ax), check_vma=False)
    run = jax.jit(lambda u: jax.lax.fori_loop(
        0, sweeps, lambda _, x: step(x), u))

    def ref_sweep(u):
        zero = jnp.zeros((rows, 1), u.dtype)   # Dirichlet domain edges
        return jacobi_sweep_ref(jnp.concatenate([zero, u, zero], axis=1))

    ref = jax.jit(lambda u: jax.lax.fori_loop(
        0, sweeps, lambda _, x: ref_sweep(x), u))

    u = random_f32(0, (1, rows, cols), NamedSharding(mesh, P(ax)))
    log(f"  grid {rows} x {cols} f32 = {rows * cols * 4} bytes, "
        f"{sweeps} sweeps")
    compiled = run.lower(u).compile()
    kernel_compiled = "tpu_custom_call" in compiled.as_text()
    log(f"  tpu_custom_call in the compiled step: {kernel_compiled}")
    if require_compiled_kernel:
        check(kernel_compiled, "Jacobi Pallas kernel ran compiled, "
                               "not interpreted")
    out = jax.block_until_ready(compiled(u))
    want = jax.block_until_ready(ref(u[0]))
    err = float(jnp.max(jnp.abs(out[0] - want)))
    scale = float(jnp.max(jnp.abs(want)))
    log(f"  max |kernel - ref| = {err!r} (max |ref| = {scale!r}), "
        f"bitwise equal: {bool(jnp.array_equal(out[0], want))}")
    check(bool(jnp.all(jnp.isfinite(out))), "Jacobi output finite")
    check(err <= 1e-6 * max(scale, 1.0),
          "Jacobi kernel agrees with kernels/jacobi/ref.py within f32 "
          "rounding")


def host_rung_one_chip(session, nelems: int) -> None:
    """The staged-host rung of the §4.6 ladder: device -> host -> device,
    bit-exact."""
    import jax
    import jax.numpy as jnp

    eng = session.engine
    dev = session.mesh.devices.flat[0]
    msg = jax.device_put(random_f32(1, (nelems,)), dev)
    log(f"  message {nelems * 4} bytes, {placement(msg)}")
    (out,) = eng._host_relay([(0, 0, nelems, jnp.float32)], [msg], [])
    jax.block_until_ready(out)
    log(f"  relayed {placement(out)}")
    check(out.devices() == {dev}, "relayed message placed on dst's device")
    check(bits_equal(out, msg), f"host rung bit-exact over {nelems * 4} B")
    check(session.stats()["health"]["host_relays"] == 1,
          "health counters saw one host relay")


def run_one_chip(devices, *, rows: int = JACOBI_ROWS,
                 cols: int = JACOBI_COLS, sweeps: int = JACOBI_SWEEPS,
                 msg_elems: int = MSG_ELEMS,
                 require_compiled_kernel: bool = True) -> None:
    import jax

    from repro.comm import CommSession

    with Phase("session"):
        session = CommSession(
            mesh=jax.sharding.Mesh(devices[:1], ("dev",)))
        log(f"  {session!r} on device {devices[0].id}")
    with Phase("jacobi"):
        jacobi_one_chip(session, rows, cols, sweeps,
                        require_compiled_kernel)
    with Phase("host_rung"):
        host_rung_one_chip(session, msg_elems)
    print_memory(devices[:1])


# -- four chips ---------------------------------------------------------------

def ppermute_reference(mesh, ax: str, x, pairs):
    """Plain single-pair ``lax.ppermute`` of ``x`` (device-stacked
    ``(ndev, nelems)``) inside ``shard_map``: the reference for a send."""
    import jax
    from jax.sharding import PartitionSpec as P
    fn = jax.jit(jax.shard_map(
        lambda v: jax.lax.ppermute(v, ax, pairs), mesh=mesh,
        in_specs=P(ax), out_specs=P(ax), check_vma=False))
    return jax.block_until_ready(fn(x))


def local_row(stacked, device):
    """Row of a device-stacked array that ``device`` holds, as an array
    committed to that device: a message where its sender keeps it."""
    (shard,) = [s for s in stacked.addressable_shards if s.device == device]
    return shard.data[0]


def send_four_chips(session, nelems: int) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    ax, mesh = session.axis_name, session.mesh
    devs = mesh.devices.flat
    stacked = random_f32(2, (session.engine.num_devices, nelems),
                         NamedSharding(mesh, P(ax)))
    for src, dst, kind in ((0, 1, "neighbour"), (0, 3, "diagonal")):
        plan = session.plan_for(src, dst, nelems, jnp.float32)
        log(f"  {kind} plan {describe_plan(plan)}")
        if kind == "diagonal":
            check(plan.num_paths > 1, "diagonal pair planned multipath")
        msg = local_row(stacked, devs[src])
        log(f"  message starts {placement(msg)}")
        out = jax.block_until_ready(session.send(msg, src, dst))
        log(f"  received y[0, {dst}] {placement(out)}")
        ref = ppermute_reference(mesh, ax, stacked, [(src, dst)])[dst]
        check(bits_equal(out, ref),
              f"send {src}->{dst} bit-exact with lax.ppermute over "
              f"{nelems * 4} B")

    a, b = local_row(stacked, devs[0]), local_row(stacked, devs[3])
    for plan in session.engine.plan_group_for(
            [(0, 3, nelems, jnp.float32), (3, 0, nelems, jnp.float32)]
            ).plans:
        log(f"  exchange plan {describe_plan(plan)}")
    fwd, rev = jax.block_until_ready(
        session.exchange([(a, 0, 3), (b, 3, 0)]))
    ref = ppermute_reference(mesh, ax, stacked, [(0, 3), (3, 0)])
    check(bits_equal(fwd, ref[3]), "exchange 0->3 bit-exact")
    check(bits_equal(rev, ref[0]), "exchange 3->0 bit-exact")
    s = session.stats()
    log(f"  dispatches={s['dispatches']} graph={s['graph']}")


def captured_jacobi_four_chips(session, rows: int, cols: int,
                               iters: int) -> None:
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.halo import jacobi_step, make_captured_jacobi_step

    ax, mesh = session.axis_name, session.mesh
    n = session.engine.num_devices
    u = random_f32(3, (n, rows, cols), NamedSharding(mesh, P(ax)))
    log(f"  domain {n} x {rows} x {cols} f32, {iters} iterations")
    step = make_captured_jacobi_step(session, rows, cols)
    entry = step.resolve()
    log(f"  captured graph: {entry.graph.num_copy_nodes} copy nodes, "
        f"{entry.graph.num_compute_nodes} compute nodes")
    eager = jax.jit(jax.shard_map(
        lambda x: jacobi_step(x[0], ax)[None], mesh=mesh,
        in_specs=P(ax), out_specs=P(ax), check_vma=False))
    before = session.stats()["dispatches"]
    got, want = u, u
    for _ in range(iters):
        (got,) = step(got)
        want = eager(want)
    jax.block_until_ready((got, want))
    check(session.stats()["dispatches"] - before == iters,
          "one dispatch per captured iteration")
    check(bits_equal(got, want),
          "captured Jacobi bitwise equal to eager jacobi_step")


def run_four_chips(devices, *, msg_elems: int = MSG_ELEMS,
                   rows: int = JACOBI_ROWS, cols: int = CAPTURED_COLS,
                   iters: int = CAPTURED_ITERS) -> None:
    import jax

    from repro.comm import CommSession

    with Phase("session"):
        session = CommSession(
            mesh=jax.sharding.Mesh(devices[:4], ("dev",)))
        log(f"  {session!r} on devices {[d.id for d in devices[:4]]}")
    with Phase("send_exchange"):
        send_four_chips(session, msg_elems)
    with Phase("captured_jacobi"):
        captured_jacobi_four_chips(session, rows, cols, iters)
    print_memory(devices[:4])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1 (default): the one-chip phase; 4: the "
                         "cross-chip sends and captured Jacobi only")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform}); "
              f"refusing to run on it", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 2

    from repro.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache(ROOT)}")
    log(f"jax {jax.__version__}, {len(devices)} devices:")
    for d in devices:
        log(f"  id={d.id} kind={d.device_kind!r} "
            f"coords={getattr(d, 'coords', None)}")
    used = devices[:args.chips]
    if args.chips == 1:
        run_one_chip(used)
    else:
        run_four_chips(used)
    print(json.dumps({"ok": True, "device": {
        "platform": used[0].platform, "kind": used[0].device_kind,
        "count": len(used)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
