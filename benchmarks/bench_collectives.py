"""Beyond-paper (paper §6 future work): multipath-striped collectives.

Compares the bidirectional-ring all-gather/reduce-scatter against the
single-direction baseline: wall-clock on the host mesh plus the structural
metric that matters on the torus — bytes crossing the busiest directional
link per step (halved by striping).

``--hierarchical`` switches to the island-aware sweep (DESIGN §3.1): the
§4.4 tier model's flat-ring vs two-level all-reduce times on a 2-island
topology, plus the executable ``two_level_all_reduce`` on a (2, 4) host
mesh validated against ``lax.psum`` over both axes. CI's bench-smoke
gates ``modeled_two_level_s <= modeled_flat_s`` on these rows.
"""

from functools import partial

from benchmarks.common import MiB, Row, timeit_us

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.comm import CommSession

#: Payload sizes (MiB) for the hierarchical model rows; --smoke keeps one.
HIER_SIZES = [8, 64]


def _uni_ring_all_gather(x, axis_name):
    n = jax.lax.axis_size(axis_name)
    i = jax.lax.axis_index(axis_name)
    cw = [(j, (j + 1) % n) for j in range(n)]
    out = jnp.zeros((n,) + x.shape, x.dtype)
    out = jax.lax.dynamic_update_slice_in_dim(out, x[None], i, axis=0)
    cur = x
    for step in range(1, n):
        cur = jax.lax.ppermute(cur, axis_name, cw)
        out = jax.lax.dynamic_update_slice(
            out, cur[None], (jnp.mod(i - step, n),) + (0,) * x.ndim)
    return out.reshape((n * x.shape[0],) + x.shape[1:])


def run() -> list[Row]:
    mesh = jax.sharding.Mesh(jax.devices(), ("dev",))
    sess = CommSession(mesh=mesh)
    n = 8
    rows = []
    for mb in (1, 8):
        nelems = mb * MiB // 4 // n
        x = jnp.asarray(np.random.RandomState(0).randn(n * 8, nelems // 8),
                        jnp.float32)

        # both sides identically jit-wrapped so the comparison is pure
        # collective time (the session driver path adds per-call key/cache
        # bookkeeping that would skew the uni-vs-bidir rows)
        uni = jax.jit(shard_map(
            lambda v: _uni_ring_all_gather(v, "dev"), mesh=mesh,
            in_specs=P("dev"), out_specs=P(None), check_vma=False))
        bi = jax.jit(shard_map(
            sess.collectives.all_gather, mesh=mesh,
            in_specs=P("dev"), out_specs=P(None), check_vma=False))
        us_uni = timeit_us(uni, x)
        us_bi = timeit_us(bi, x)
        sess.all_gather(x)   # driver path: compiled once into the plan cache
        rows.append(Row(f"allgather/{mb}MiB/uni_ring", us_uni,
                        "1link/step"))
        rows.append(Row(f"allgather/{mb}MiB/bidir_ring", us_bi,
                        "2links/step"))
        # structural: per-step busiest-link bytes halve with striping
        shard_bytes = x.nbytes // n
        rows.append(Row(
            f"allgather/{mb}MiB/busiest_link_bytes_per_step", 0.0,
            f"uni={shard_bytes}B,bidir={shard_bytes // 2}B"))

        rs = jax.jit(shard_map(
            sess.collectives.reduce_scatter, mesh=mesh,
            in_specs=P(None), out_specs=P("dev"), check_vma=False))
        xr = jnp.asarray(np.random.RandomState(1).randn(n * 8, nelems // 8),
                         jnp.float32)
        rows.append(Row(f"reducescatter/{mb}MiB/bidir_ring",
                        timeit_us(rs, xr), "2links/step"))
        sess.reduce_scatter(xr)
    rows.append(Row("collectives/plan_cache", 0.0,
                    "hits={hits},misses={misses}".format(
                        **sess.stats()["cache"])))
    return rows


def run_hierarchical() -> list[Row]:
    """Island-aware sweep: modeled flat vs two-level all-reduce on a
    2-island × 4-device topology + the executable decomposition."""
    from repro.comm.collectives import (select_all_reduce_strategy,
                                        two_level_all_reduce)
    from repro.core.topology import Topology

    topo = Topology.hierarchical(2, 4, name="hier2x4")
    rows = []
    for mb in HIER_SIZES:
        nbytes = mb * MiB
        chosen, times = select_all_reduce_strategy(topo, nbytes)
        speedup = times["flat"] / max(times["two_level"], 1e-12)
        rows.append(Row(
            f"hier/allreduce/{mb}MiB/modeled", times["two_level"] * 1e6,
            f"chosen={chosen},flat={times['flat'] * 1e6:.1f}us,"
            f"speedup={speedup:.2f}x",
            {"islands": topo.num_islands,
             "modeled_flat_s": times["flat"],
             "modeled_two_level_s": times["two_level"],
             "chosen": chosen}))

    # Executable two-level decomposition on the (pod, dev) host mesh,
    # validated against the joint psum before timing.
    mesh = jax.make_mesh((2, 4), ("pod", "dev"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    x = jnp.asarray(np.random.RandomState(2).randn(16, 256), jnp.float32)
    two = jax.jit(shard_map(
        partial(two_level_all_reduce, inter_axis="pod", intra_axis="dev"),
        mesh=mesh, in_specs=P("dev"), out_specs=P("dev"), check_vma=False))
    ref = jax.jit(shard_map(
        lambda v: jax.lax.psum(v, ("pod", "dev")),
        mesh=mesh, in_specs=P("dev"), out_specs=P("dev"), check_vma=False))
    np.testing.assert_allclose(np.asarray(two(x)), np.asarray(ref(x)),
                               rtol=1e-5)
    rows.append(Row("hier/allreduce/exec/two_level", timeit_us(two, x),
                    "2x4_mesh", {"matches_psum": True}))
    rows.append(Row("hier/allreduce/exec/flat_psum", timeit_us(ref, x),
                    "2x4_mesh"))
    return rows


def main() -> None:
    import argparse
    import json

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--hierarchical", action="store_true",
                    help="island-aware sweep (flat vs two-level rows)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes only (CI smoke step)")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="also write rows as a JSON artifact")
    args = ap.parse_args()
    if args.smoke:
        HIER_SIZES[:] = HIER_SIZES[:1]
    rows = run_hierarchical() if args.hierarchical else run()
    print("name,us_per_call,derived")
    for row in rows:
        print(row.csv(), flush=True)
    if args.json:
        payload = [{"name": r.name, "us_per_call": round(r.us, 2),
                    "derived": r.derived, **r.extra} for r in rows]
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=1)
        print(f"# wrote {len(payload)} rows to {args.json}", flush=True)


if __name__ == "__main__":
    main()
