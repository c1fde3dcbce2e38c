"""Quickstart: the unified comm session API (multi-path + plan caching).

One ``CommSession`` owns the topology, the path policy, the planner, and
the compiled-plan cache — every subsystem (training, serving, benchmarks)
drives communication through it.

Run:  PYTHONPATH=src python examples/quickstart.py
"""

import os

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp
import numpy as np

from repro.comm import CommConfig, CommSession
from repro.core import (Topology, build_schedule, effective_bandwidth_gbps,
                        estimate_transfer_time_s)


def main():
    # 1) describe the node: 4 GPUs, NVLink full mesh + PCIe host (Beluga)
    #    and open a session on it (greedy bandwidth-proportional policy)
    sess = CommSession(CommConfig(max_paths=4),
                       topology=Topology.full_mesh(4))
    topo = sess.topology

    # 2) plan a 64 MiB transfer GPU0 -> GPU1
    plan = sess.plan(0, 1, 64 << 20, max_paths=3)
    print(f"plan: {plan.num_paths} paths, {plan.num_nodes} copy nodes "
          f"(policy={sess.policy.name})")
    for pa in plan.paths:
        print(f"  {pa.route.kind:14s} via={pa.route.via} "
              f"share={pa.nbytes >> 20}MiB chunks={pa.num_chunks}")
    print(f"schedule: {len(build_schedule(plan))} chunk tasks")

    # 3) modeled bandwidth: single vs multi-path (paper Fig. 6)
    single = sess.plan(0, 1, 64 << 20, max_paths=1)
    print(f"modeled: single {effective_bandwidth_gbps(single, topo):.0f} "
          f"GB/s -> multipath "
          f"{effective_bandwidth_gbps(plan, topo):.0f} GB/s "
          f"({estimate_transfer_time_s(single, topo) / estimate_transfer_time_s(plan, topo):.2f}x)")

    # 4) the offline tuner (paper §4.4) searches paths × chunks × host
    best = sess.tune(0, 1, 64 << 20)
    print(f"tuned: {best.num_paths} paths, {best.num_nodes} nodes")

    # 5) execute for real on the devices this process has, twice (cache
    #    hit): the last device receives from the first
    n = len(jax.devices())
    if n < 2:
        print(f"execution needs >= 2 devices, found {n}; stopping here")
        return
    last = n - 1
    run = CommSession(topology=Topology.full_mesh(n, with_host=False))
    msg = jnp.arange(1 << 20, dtype=jnp.float32)
    out = run.send(msg, 0, last)
    assert np.array_equal(np.asarray(out), np.asarray(msg))
    run.send(msg, 0, last)

    # 5b) concurrent messages: one fused transfer group = one compiled
    # launch, planned contention-aware (exchange patterns stay
    # link-disjoint; see DESIGN.md §5)
    fwd, rev = run.exchange([(msg, 0, last), (msg * 2, last, 0)])
    assert np.array_equal(np.asarray(rev), np.asarray(msg * 2))
    print(f"fused 2-message exchange OK; dispatches={run.stats()['dispatches']}")

    # 6) collectives ride the same session + plan cache
    x = jnp.asarray(np.random.RandomState(0).randn(n, 16), jnp.float32)
    gathered = run.all_gather(x)
    assert np.allclose(np.asarray(gathered), np.asarray(x))
    print(f"executed transfer + all-gather OK; "
          f"plan cache: {run.stats()['cache']}")
    key, compiled = next(iter(run.cache._store.items()))
    life = compiled.lifecycle
    print(f"lifecycle: trace {life.trace_ns/1e6:.1f}ms, "
          f"lower {life.lower_ns/1e6:.1f}ms, "
          f"instantiate {life.compile_ns/1e6:.1f}ms, "
          f"mean launch {life.mean_launch_ns/1e6:.2f}ms "
          f"({life.launches} launches)")


if __name__ == "__main__":
    main()
