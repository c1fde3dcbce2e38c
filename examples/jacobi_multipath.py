"""The paper's application (§5.4): distributed Jacobi solver with
multi-path halo exchange.

Run:  PYTHONPATH=src python examples/jacobi_multipath.py [--iters 200]

``--captured`` additionally runs the whole-iteration capture mode
(DESIGN §2.4): sweep + halo exchange recorded as ONE heterogeneous
transfer graph via ``session.capture``, so every iteration is exactly
one engine dispatch (the script prints the dispatch count to prove it).
"""

import os

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from jax import shard_map
from repro.core.halo import jacobi_step, make_captured_jacobi_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=200)
    ap.add_argument("--rows", type=int, default=8)
    ap.add_argument("--cols-per-rank", type=int, default=4096)
    ap.add_argument("--captured", action="store_true",
                    help="also run the §2.4 whole-iteration capture: "
                         "sweep + exchange as ONE graph, one dispatch "
                         "per iteration")
    ap.add_argument("--schedule", default=None,
                    help="chunk-interleaving schedule for the captured "
                         "graph (round_robin/depth_first/critical_path/"
                         "auto)")
    args = ap.parse_args()

    mesh = jax.sharding.Mesh(jax.devices(), ("dev",))
    n = len(jax.devices())
    rng = np.random.RandomState(0)
    u0 = jnp.asarray(rng.randn(n, args.rows, args.cols_per_rank),
                     jnp.float32)

    def solver(multipath):
        def local(u):
            def sweep(u, _):
                return jacobi_step(u, "dev", multipath=multipath), None
            u, _ = jax.lax.scan(sweep, u[0], None, length=args.iters)
            return u[None]
        return jax.jit(shard_map(local, mesh=mesh, in_specs=P("dev"),
                                 out_specs=P("dev"), check_vma=False))

    for multipath in (False, True):
        f = solver(multipath)
        u = jax.block_until_ready(f(u0))   # compile + run once
        t0 = time.perf_counter()
        u = jax.block_until_ready(f(u0))
        dt = time.perf_counter() - t0
        resid = float(jnp.max(jnp.abs(u)))
        tag = "multipath" if multipath else "single-path"
        print(f"{tag:12s}: {args.iters} iters in {dt:.3f}s "
              f"({dt / args.iters * 1e3:.2f} ms/iter), max|u|={resid:.4f}")

    if args.captured:
        from repro.comm import CommSession

        session = CommSession(mesh=mesh)
        captured = make_captured_jacobi_step(
            session, args.rows, args.cols_per_rank,
            schedule=args.schedule)
        entry = captured.resolve()      # lower + schedule + compile once
        g = entry.graph
        jax.block_until_ready(captured(u0)[0])       # warm launch
        session.stats(reset=True)
        u = u0
        t0 = time.perf_counter()
        for _ in range(args.iters):
            u = captured(u, block=False)[0]
        u = jax.block_until_ready(u)
        dt = time.perf_counter() - t0
        dispatches = session.stats()["dispatches"]
        resid = float(jnp.max(jnp.abs(u)))
        print(f"{'captured':12s}: {args.iters} iters in {dt:.3f}s "
              f"({dt / args.iters * 1e3:.2f} ms/iter), max|u|={resid:.4f}")
        print(f"  one heterogeneous graph: {g.num_copy_nodes} copy + "
              f"{g.num_compute_nodes} compute nodes, schedule="
              f"{entry.schedule}; {dispatches} dispatches for "
              f"{args.iters} iterations (exactly one per step)")
    print("halo exchange over both direct and diagonal (staged) links — "
          "see benchmarks/bench_jacobi.py for the Beluga-model speedups "
          "and benchmarks/bench_step_capture.py for captured vs "
          "uncaptured dispatch cost")


if __name__ == "__main__":
    main()
