"""Paper Fig. 12 — Jacobi solver runtime speedup: single-path vs multipath
halo exchange. Executes for real on the 8-device host mesh (wall-clock) and
reports the Beluga link-model speedup for the paper's problem sizes."""

from benchmarks.common import MiB, Row, timeit_us

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.comm import CommSession
from jax import shard_map
from repro.core import (Topology, estimate_group_time_s,
                        estimate_transfer_time_s)
from repro.core.halo import jacobi_step


def _solver(mesh, multipath, iters=10):
    def body(u):
        def sweep(u, _):
            return jacobi_step(u, "dev", multipath=multipath), None
        u, _ = jax.lax.scan(sweep, u, None, length=iters)
        return u

    def local(u):
        return body(u[0])[None]

    return jax.jit(shard_map(local, mesh=mesh, in_specs=P("dev"),
                             out_specs=P("dev"), check_vma=False))


def run() -> list[Row]:
    rows = []
    mesh = jax.sharding.Mesh(jax.devices(), ("dev",))
    u = jnp.asarray(np.random.RandomState(0).randn(8, 8, 4096), jnp.float32)
    for multipath in (False, True):
        f = _solver(mesh, multipath)
        us = timeit_us(f, u, iters=3, warmup=1)
        tag = "multipath" if multipath else "singlepath"
        rows.append(Row(f"jacobi_exec/8x32768/{tag}", us, "10iters"))

    # paper-scale analytic model: 4 ranks, vertical dim 8, horizontal 2^23..2^30
    topo = Topology.full_mesh(4)
    sess = CommSession(topology=topo)
    for log2w in (23, 26, 28, 30):
        total = 8 * (1 << log2w) * 4          # fp32 domain bytes
        boundary = total // 4 // (1 << 5)     # 256MB at 8GB (paper §5.4)
        boundary = max(4096, 8 * (1 << log2w) // 4 // 8 * 4 // 1)
        # per-iteration comm: each rank exchanges one boundary column block
        # with each neighbour; compute time modeled at 819 GB/s local sweep
        nbytes = 8 * 4 * (1 << log2w) // 4 // 8  # col-block bytes per rank
        nbytes = max(nbytes, 4096)
        t1 = 2 * estimate_transfer_time_s(
            sess.plan(0, 1, nbytes, max_paths=1), topo,
            compiled_plan=False)
        t2 = 2 * estimate_transfer_time_s(
            sess.plan(0, 1, nbytes, max_paths=2, num_chunks=4), topo,
            compiled_plan=True)
        compute = (total / 4) * 5 / (819e9)   # 5-point sweep reads
        sp = (compute + t1) / (compute + t2)
        rows.append(Row(f"jacobi_model/2^{log2w}cols/2path_speedup", 0.0,
                        f"{sp:.2f}x(paper<=1.28x)"))

        # transfer-group halo: all 8 boundary messages of the 4-rank ring
        # (±1 neighbours) planned jointly and fused into ONE launch, vs 8
        # independently-planned back-to-back dispatches per iteration.
        reqs = []
        for i in range(4):
            reqs += [(i, (i + 1) % 4, nbytes), (i, (i - 1) % 4, nbytes)]
        group = sess.plan_group(reqs, num_chunks=4)
        t_grp = estimate_group_time_s(group, topo, fused=True)
        indep = [sess.plan(s, d, n, num_chunks=4) for s, d, n in reqs]
        t_ind = estimate_group_time_s(indep, topo, fused=False)
        rows.append(Row(f"jacobi_halo_group/2^{log2w}cols/fused_speedup",
                        0.0, f"{t_ind / t_grp:.2f}x"))
    return rows
