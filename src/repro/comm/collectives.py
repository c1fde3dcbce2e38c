"""Multipath-striped collectives (BEYOND-PAPER — the paper's §6 future work).

The paper stripes *point-to-point* messages across idle links. The same
insight applies to collectives on a torus/ring: a unidirectional ring
all-gather uses only one direction of each bidirectional ICI link, leaving
half the injection bandwidth idle. These implementations stripe the payload
across **both ring directions** (2 paths), which halves the bytes crossing
any single directional link — the collective-roofline term drops ~2×.

All functions are written for use inside ``shard_map`` over a named mesh
axis, and are validated against ``jax.lax`` references in
``tests/test_collectives.py``. For axis-bound access (and driver-level
compiled launches that share a session's plan cache) see
:class:`repro.comm.session.CommSession`.

Hierarchy (DESIGN §3.1): on topologies with more than one island the flat
ring's bottleneck is the inter-node tier. :func:`two_level_all_reduce`
decomposes the all-reduce into an intra-island multipath reduce-scatter,
an inter-island ring over the shards, and an intra-island multipath
all-gather; :func:`modeled_all_reduce_s` prices both layouts under the
§4.4 tier model and :func:`select_all_reduce_strategy` arbitrates.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from jax.lax import axis_size
from repro.core.topology import HOST, Topology


def _ring_perms(n: int):
    cw = [(i, (i + 1) % n) for i in range(n)]
    ccw = [(i, (i - 1) % n) for i in range(n)]
    return cw, ccw


def bidir_ring_all_gather(x: jax.Array, axis_name: str) -> jax.Array:
    """All-gather along ``axis_name`` using both ring directions.

    ``x`` is the local shard ``(s, ...)``; returns ``(N*s, ...)`` in device
    order — equivalent to ``lax.all_gather(x, axis_name, tiled=True)``
    (validated against it in ``tests/test_collectives.py``).
    Half the features travel clockwise, half counter-clockwise, so each of
    the N-1 steps uses both directional links of the ring simultaneously.
    """
    n = axis_size(axis_name)
    if n == 1:
        return x
    i = lax.axis_index(axis_name)
    cw, ccw = _ring_perms(n)

    f = x.shape[-1]
    f0 = f // 2
    if f0 == 0:  # nothing to split — degrade to single direction
        f0 = f
    h0, h1 = x[..., :f0], x[..., f0:]

    out = jnp.zeros((n,) + x.shape, x.dtype)
    out = lax.dynamic_update_slice_in_dim(out, x[None], i, axis=0)
    cur0, cur1 = h0, h1
    for step in range(1, n):
        cur0 = lax.ppermute(cur0, axis_name, cw)
        src0 = jnp.mod(i - step, n)
        out = lax.dynamic_update_slice(
            out, cur0[None], (src0,) + (0,) * x.ndim)
        if h1.shape[-1]:
            cur1 = lax.ppermute(cur1, axis_name, ccw)
            src1 = jnp.mod(i + step, n)
            out = lax.dynamic_update_slice(
                out, cur1[None], (src1,) + (0,) * (x.ndim - 1) + (f0,))
    return out.reshape((n * x.shape[0],) + x.shape[1:])


def bidir_ring_reduce_scatter(x: jax.Array, axis_name: str) -> jax.Array:
    """Reduce-scatter (sum) along ``axis_name`` using both ring directions.

    ``x`` is the full local operand ``(N*s, ...)``; returns the reduced shard
    ``(s, ...)`` owned by this device — equivalent to
    ``lax.psum_scatter(x, axis_name, tiled=True)`` (validated against it
    in ``tests/test_collectives.py``).
    """
    n = axis_size(axis_name)
    if n == 1:
        return x
    i = lax.axis_index(axis_name)
    cw, ccw = _ring_perms(n)
    s = x.shape[0] // n
    blocks = x.reshape((n, s) + x.shape[1:])

    f = x.shape[-1] if x.ndim > 1 else 1
    f0 = f // 2 if x.ndim > 1 else 0

    def blk(idx, lo, hi):
        b = lax.dynamic_index_in_dim(blocks, jnp.mod(idx, n), axis=0,
                                     keepdims=False)
        if x.ndim > 1 and hi is not None:
            b = b[..., lo:hi]
        return b

    if f0 == 0:
        # Single-direction fallback (narrow features).
        acc = blk(i - 1, 0, None)
        for t in range(1, n):
            acc = lax.ppermute(acc, axis_name, cw)
            acc = acc + blk(i - t - 1, 0, None)
        return acc

    acc0 = lax.dynamic_index_in_dim(
        blocks[..., :f0], jnp.mod(i - 1, n), axis=0, keepdims=False)
    acc1 = lax.dynamic_index_in_dim(
        blocks[..., f0:], jnp.mod(i + 1, n), axis=0, keepdims=False)
    for t in range(1, n):
        acc0 = lax.ppermute(acc0, axis_name, cw)
        acc0 = acc0 + lax.dynamic_index_in_dim(
            blocks[..., :f0], jnp.mod(i - t - 1, n), axis=0, keepdims=False)
        acc1 = lax.ppermute(acc1, axis_name, ccw)
        acc1 = acc1 + lax.dynamic_index_in_dim(
            blocks[..., f0:], jnp.mod(i + t + 1, n), axis=0, keepdims=False)
    return jnp.concatenate([acc0, acc1], axis=-1)


def multipath_all_reduce(x: jax.Array, axis_name: str) -> jax.Array:
    """All-reduce = bidirectional reduce-scatter + bidirectional all-gather.

    Equivalent to ``lax.psum(x, axis_name)`` (validated against it in
    ``tests/test_collectives.py``). Requires ``x.shape[0]`` to be
    divisible by the axis size (pad upstream otherwise).
    """
    n = axis_size(axis_name)
    if n == 1:
        return x
    shard = bidir_ring_reduce_scatter(x, axis_name)
    return bidir_ring_all_gather(shard, axis_name)


def multipath_all_to_all(x: jax.Array, axis_name: str) -> jax.Array:
    """All-to-all along ``axis_name`` with opposite-direction step pairing.

    ``x`` has leading dim ``N`` (one block per destination); returns the same
    shape with block ``j`` received from device ``j`` — equivalent to
    ``lax.all_to_all(x, axis_name, 0, 0, tiled=False)`` on a block-indexed
    operand (validated against it in ``tests/test_collectives.py``).
    Shift ``+s`` and ``+(N-s)`` travel opposite directions on the
    physical ring, so pairing them stripes each step across both directions
    (the MoE expert-parallel application of the paper's idea).
    """
    n = axis_size(axis_name)
    if n == 1:
        return x
    i = lax.axis_index(axis_name)
    out = jnp.zeros_like(x)
    # keep own block
    own = lax.dynamic_index_in_dim(x, i, axis=0, keepdims=True)
    out = lax.dynamic_update_slice_in_dim(out, own, i, axis=0)
    for s in range(1, n):
        # send block destined to (i+s) — a single full permutation; shifts s
        # and n-s are emitted adjacently so the scheduler can overlap the two
        # opposite ring directions.
        perm = [(j, (j + s) % n) for j in range(n)]
        block = lax.dynamic_index_in_dim(x, jnp.mod(i + s, n), axis=0,
                                         keepdims=True)
        recv = lax.ppermute(block, axis_name, perm)
        out = lax.dynamic_update_slice_in_dim(
            out, recv, jnp.mod(i - s, n), axis=0)
    return out


def psum_via_multipath(x: jax.Array, axis_name: str) -> jax.Array:
    """Drop-in ``psum`` for arbitrary-shape operands.

    Flattens, pads to a multiple of ``2 * axis_size``, multipath-all-reduces,
    and restores the shape (validated against ``lax.psum`` in
    ``tests/test_collectives.py``). Used by the manual-collectives
    training mode.

    The operand is reshaped to two feature columns — NOT a column vector:
    the ring algorithms split the last dim across the two ring directions,
    and a single-column operand (f0 = 0) would silently fall back to the
    one-directional ring, forfeiting the multipath striping.
    """
    n = axis_size(axis_name)
    if n == 1:
        return x
    flat = x.reshape(-1)
    pad = (-flat.shape[0]) % (2 * n)
    if pad:
        flat = jnp.pad(flat, (0, pad))
    red = multipath_all_reduce(flat.reshape(-1, 2), axis_name)
    red = red.reshape(-1)[:x.size]
    return red.reshape(x.shape)


def two_level_all_reduce(x: jax.Array, inter_axis: str,
                         intra_axis: str) -> jax.Array:
    """Hierarchical all-reduce: intra-island multipath reduce-scatter,
    inter-island ring all-reduce over the shards, intra-island multipath
    all-gather (DESIGN §3.1).

    ``intra_axis`` names the fast (intra-node) mesh axis, ``inter_axis``
    the slow (inter-node) one. Equivalent to
    ``lax.psum(x, (inter_axis, intra_axis))`` — validated against that
    reference in ``tests/test_collectives.py``. Only ``nbytes / M``
    (M = island size) crosses the slow tier, which is why the §4.4 model
    prices it below the flat ring whenever the inter tier is the
    bottleneck. Requires ``x.shape[0]`` divisible by the ``intra_axis``
    size (pad upstream otherwise).
    """
    shard = bidir_ring_reduce_scatter(x, intra_axis)
    shard = psum_via_multipath(shard, inter_axis)
    return bidir_ring_all_gather(shard, intra_axis)


# -- §4.4 tier model: flat ring vs two-level decomposition -------------------

def tier_bandwidths_gbps(topo: Topology) -> tuple[float, float | None]:
    """Bottleneck bandwidth per tier: ``(intra_gbps, inter_gbps)``.

    Minimum directional-link bandwidth inside islands and across them
    (``None`` when the topology has no inter-island links). Host links
    are excluded — host staging is not a collective tier. Bandwidths are
    read through :meth:`~repro.core.topology.Topology.link`, so a live
    calibration profile's fitted terms (keyed by the topology digest)
    flow into the collective model automatically.
    """
    intra: list[float] = []
    inter: list[float] = []
    for key in topo.links:
        if HOST in key:
            continue
        link = topo.link(*key)
        (inter if topo.is_inter_island(*key) else intra).append(
            link.bandwidth_gbps)
    if not intra:
        raise ValueError(f"topology {topo.name} has no device links")
    return min(intra), (min(inter) if inter else None)


def modeled_all_reduce_s(topo: Topology, nbytes: int,
                         strategy: str = "flat") -> float:
    """Modeled seconds for an ``nbytes`` all-reduce over all devices.

    ``strategy="flat"`` prices the bidirectional ring over every device:
    ``2(N-1)`` steps of ``nbytes / 2N`` each, bottlenecked by the slowest
    tier the ring must cross (the inter-node tier on hierarchical
    topologies, plus :data:`~repro.core.pipelining.INTER_NODE_LATENCY_NS`
    per step). ``strategy="two_level"`` prices the
    :func:`two_level_all_reduce` decomposition — intra steps at the intra
    tier, only the ``nbytes / M`` shard crossing islands — and is
    ``inf`` when islands are disconnected. Both use the same per-tier
    bandwidths (:func:`tier_bandwidths_gbps`), so the comparison the
    selection contract rests on is apples-to-apples; validated in
    ``tests/test_collectives.py`` and gated in CI's bench-smoke.
    """
    from repro.core.pipelining import INTER_NODE_LATENCY_NS

    if nbytes <= 0:
        raise ValueError("nbytes must be positive")
    n = topo.num_devices
    if n <= 1:
        return 0.0
    bw_intra, bw_inter = tier_bandwidths_gbps(topo)
    islands = topo.islands()
    num_islands = len(islands)
    lat = INTER_NODE_LATENCY_NS / 1e9 if num_islands > 1 else 0.0
    if strategy == "flat":
        bottleneck = bw_inter if (num_islands > 1 and bw_inter) else bw_intra
        steps = 2 * (n - 1)
        return steps * ((nbytes / (2 * n)) / (bottleneck * 1e9) + lat)
    if strategy != "two_level":
        raise ValueError(f"unknown all-reduce strategy {strategy!r}")
    if num_islands == 1:
        return modeled_all_reduce_s(topo, nbytes, "flat")
    if bw_inter is None:
        return float("inf")
    m = max(len(devs) for devs in islands)
    t_intra = 2 * (m - 1) * (nbytes / (2 * m)) / (bw_intra * 1e9)
    shard = nbytes / m
    t_inter = 2 * (num_islands - 1) * (
        (shard / (2 * num_islands)) / (bw_inter * 1e9) + lat)
    return t_intra + t_inter


def select_all_reduce_strategy(topo: Topology, nbytes: int,
                               strategy: str = "auto"
                               ) -> tuple[str, dict[str, float]]:
    """Pick the all-reduce layout for ``topo``: ``(chosen, times_s)``.

    ``strategy="auto"`` (the selection contract): flat on single-island
    topologies; on hierarchical ones the two-level decomposition wins iff
    it models strictly faster under :func:`modeled_all_reduce_s`.
    ``"flat"`` / ``"two_level"`` force the layout but still return both
    modeled times, so ``session.describe()`` and the benchmarks can
    report the flat-vs-hierarchical delta either way.

    Degradation invariant (DESIGN §4.6): a forced ``"two_level"``
    falls back to ``"flat"`` when the two-level decomposition models
    infinite time — every egress link of some island has failed, so
    the inter-island exchange phase cannot run. The fault model feeds
    this automatically: failed links vanish from ``topo.links`` and
    degraded links price at their scaled bandwidth, so the modeled
    times here already reflect the surviving fabric.
    """
    times = {"flat": modeled_all_reduce_s(topo, nbytes, "flat"),
             "two_level": modeled_all_reduce_s(topo, nbytes, "two_level")}
    if strategy == "two_level" and times["two_level"] == float("inf"):
        # Egress fabric gone — serve the reduction on the flat ring
        # rather than raising mid-collective.
        return "flat", times
    if strategy in ("flat", "two_level"):
        return strategy, times
    if strategy != "auto":
        raise ValueError(f"unknown all-reduce strategy {strategy!r}")
    if topo.num_islands > 1 and times["two_level"] < times["flat"]:
        return "two_level", times
    return "flat", times
