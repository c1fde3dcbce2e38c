"""Production mesh + topology construction.

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state). The single-pod mesh is a 16×16 = 256-chip v5e pod
(data × model); the multi-pod mesh adds a leading pod axis (2 pods = 512
chips) carrying pure data parallelism across the DCN.

The comm-model side of the same decision lives here too:
``make_production_topology`` builds the matching :class:`Topology` — flat
16×16 ICI torus for one pod, or two torus islands joined by DCN links
(island-aware, DESIGN §3.1) for the multi-pod mesh — and
``production_launch_spec(arch)`` resolves both from an architecture's
``multi_pod`` hint, so the launcher, the dry-run, and the planner all
agree on which machine a config runs on.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

from repro.configs.base import ArchConfig
from repro.core.topology import Topology

#: Per-chip DCN egress links joining two pods (v5e: a slice of hosts own
#: the data-center NICs), and the per-link DCN bandwidth class.
DCN_EGRESS_PER_POD = 4
DCN_LINK_GBPS = 25.0


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def production_mesh_shape(*, multi_pod: bool = False
                          ) -> tuple[tuple[int, ...], tuple[str, ...]]:
    """The (shape, axis names) ``make_production_mesh`` would build —
    resolvable without 256/512 placeholder devices (tests, specs)."""
    if multi_pod:
        return (2, 16, 16), ("pod", "data", "model")
    return (16, 16), ("data", "model")


def make_production_topology(*, multi_pod: bool = False) -> Topology:
    """The comm :class:`Topology` matching :func:`make_production_mesh`.

    Single pod: the flat 16×16 ICI torus (one island). Multi-pod: two
    such torus islands joined by :data:`DCN_EGRESS_PER_POD` DCN links —
    the planner's island-aware routing then keeps intra-pod traffic on
    ICI and stages cross-pod transfers through exactly one DCN hop.
    """
    if not multi_pod:
        return Topology.torus2d(16, 16, name="pod16x16")
    return Topology.hierarchical(
        2, 256, intra="torus", torus_shape=(16, 16),
        inter_gbps=DCN_LINK_GBPS, inter_kind="dcn",
        egress_per_island=DCN_EGRESS_PER_POD, name="pods2x16x16")


def production_launch_spec(arch: ArchConfig) -> dict:
    """Resolve the launch-time machine for ``arch``: mesh shape/axes plus
    the island-aware topology, all keyed off ``arch.multi_pod`` (the
    configs' honest statement of whether one pod's HBM suffices)."""
    shape, axes = production_mesh_shape(multi_pod=arch.multi_pod)
    return {
        "arch": arch.name,
        "multi_pod": arch.multi_pod,
        "mesh_shape": shape,
        "mesh_axes": axes,
        "topology": make_production_topology(multi_pod=arch.multi_pod),
    }


def make_host_mesh(shape=None, axes=("data", "model")) -> jax.sharding.Mesh:
    """Mesh over whatever devices exist (tests / smoke runs)."""
    n = len(jax.devices())
    if shape is None:
        shape = (1, n)
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))
