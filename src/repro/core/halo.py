"""Multi-path halo exchange — the paper's Jacobi application (§5.4, Fig. 11).

A 1-D ring decomposition (the paper uses 4 ranks, each exchanging boundary
columns with its two neighbours). With single-path communication only the
±1 ring links carry traffic and the "diagonal" links sit idle (Fig. 11a).
The multipath mode splits each boundary in half and stages the second half
through the diagonal device (Fig. 11b), engaging the otherwise-idle links.

Contention note (paper §5.4): on Beluga each GPU pair has *two* NVLink
sublinks, which is what makes the staged hop-2 contention-free with the
opposite-direction direct sends; our aggregated-link topology models this as
shared doubled bandwidth rather than strict link exclusivity (DESIGN.md §7.3).
"""

from __future__ import annotations

from typing import TYPE_CHECKING

import jax
import jax.numpy as jnp
from jax import lax

from jax.lax import axis_size

if TYPE_CHECKING:  # pragma: no cover - typing only
    from repro.comm.session import CommSession


def _shift_perm(n: int, shift: int):
    return [(i, (i + shift) % n) for i in range(n)]


def halo_exchange_ring(left_bnd: jax.Array, right_bnd: jax.Array,
                       axis_name: str, *, multipath: bool = False,
                       ) -> tuple[jax.Array, jax.Array]:
    """Exchange boundaries with ring neighbours along ``axis_name``.

    ``left_bnd``/``right_bnd`` are this shard's own boundary slices. Returns
    ``(left_halo, right_halo)``: the right boundary of the left neighbour and
    the left boundary of the right neighbour.

    ``multipath=True`` splits each boundary into two stripes: the first goes
    over the direct ±1 link, the second stages through the device two hops
    around the ring (the idle diagonal on a 4-device node).
    """
    n = axis_size(axis_name)
    if n == 1:
        return right_bnd, left_bnd

    if not multipath or n < 3:
        left_halo = lax.ppermute(right_bnd, axis_name, _shift_perm(n, 1))
        right_halo = lax.ppermute(left_bnd, axis_name, _shift_perm(n, -1))
        return left_halo, right_halo

    def split(b):
        h = b.shape[-1] // 2
        if h == 0:
            return b, b[..., :0]
        return b[..., :h], b[..., h:]

    # to the RIGHT neighbour: my right boundary becomes their left halo.
    r0, r1 = split(right_bnd)
    right_direct = lax.ppermute(r0, axis_name, _shift_perm(n, 1))
    staged = lax.ppermute(r1, axis_name, _shift_perm(n, 2))      # hop-1: diag
    right_staged = lax.ppermute(staged, axis_name, _shift_perm(n, -1))  # hop-2
    left_halo = jnp.concatenate([right_direct, right_staged], axis=-1)

    # to the LEFT neighbour: my left boundary becomes their right halo.
    l0, l1 = split(left_bnd)
    left_direct = lax.ppermute(l0, axis_name, _shift_perm(n, -1))
    staged = lax.ppermute(l1, axis_name, _shift_perm(n, -2))     # hop-1: diag
    left_staged = lax.ppermute(staged, axis_name, _shift_perm(n, 1))   # hop-2
    right_halo = jnp.concatenate([left_direct, left_staged], axis=-1)
    return left_halo, right_halo


def halo_exchange_group(session: "CommSession", blocks: jax.Array
                        ) -> tuple[jax.Array, jax.Array]:
    """Driver-level ring halo exchange as ONE fused transfer group.

    ``blocks`` is the column-decomposed domain, shape ``(n, rows, cols)``
    (one block per rank). Every rank's two boundary columns ride a single
    ``2n``-message group — the paper's 4-rank pattern is a 4-transfer
    group per shift direction — planned jointly (the ring's directional
    links are disjoint, so the group is link-exclusive) and launched once,
    instead of ``2n`` independent sends. Returns ``(left_halos,
    right_halos)``, shape ``(n, rows, 1)`` each: rank *i*'s left halo is
    rank *i-1*'s right boundary and vice versa (periodic; apply Dirichlet
    masking downstream as :func:`jacobi_step` does).
    """
    n = blocks.shape[0]
    if n == 1:
        return blocks[:, :, -1:], blocks[:, :, :1]
    items = []
    for i in range(n):
        items.append((blocks[i, :, -1:], i, (i + 1) % n))  # → right nbr
        items.append((blocks[i, :, :1], i, (i - 1) % n))   # → left nbr
    received = session.exchange(items)
    left_halos = jnp.stack([received[2 * ((i - 1) % n)] for i in range(n)])
    right_halos = jnp.stack([received[2 * ((i + 1) % n) + 1]
                             for i in range(n)])
    return left_halos, right_halos


def make_captured_jacobi_step(session: "CommSession", rows: int, cols: int,
                              dtype=jnp.float32, *,
                              schedule: str | None = None,
                              max_paths: int | None = None,
                              num_chunks: int | None = None):
    """Capture one whole Jacobi iteration (halo exchange + sweep) as ONE
    heterogeneous graph — the reference ``session.capture`` idiom.

    The returned :class:`~repro.comm.capture.CapturedStep` takes the
    column-decomposed domain ``(n, rows, cols)`` and returns the swept
    domain, same shape, in ONE compiled launch: boundary extraction and
    the 5-point stencil are compute nodes, the ``2n``-message ring
    exchange is planned jointly (``max_paths``/``num_chunks`` as in
    :meth:`~repro.comm.session.CommSession.exchange`), and the scheduler
    pass interleaves the copies into the compute gaps. The sweep applies
    *exactly* the eager :func:`jacobi_step` operations (same Dirichlet
    masking, same stencil), and each halo is joined from the exchange's
    reception buffers by exact zero-sum — numerics are identical to the
    eager path, which ``tests/test_capture.py`` asserts bitwise.
    """
    ax = session.axis_name
    n = session.engine.num_devices
    if n < 2:
        raise ValueError("captured Jacobi needs >= 2 devices (the ring "
                         "exchange cannot self-send)")

    def build(cap):
        u = cap.input((rows, cols), dtype)
        right, left = cap.kernel(
            lambda u_: (u_[:, -1], u_[:, 0]), u, name="halo_slices",
            flops=0)
        sends = ([(right, i, (i + 1) % n) for i in range(n)]
                 + [(left, i, (i - 1) % n) for i in range(n)])
        recvs = cap.exchange(sends, max_paths=max_paths,
                             num_chunks=num_chunks)

        def sweep(u_, *halos):
            # device j's left halo is j-1's right boundary: of the n
            # right-going receptions exactly one is nonzero locally.
            left_halo = halos[0]
            for h in halos[1:n]:
                left_halo = left_halo + h
            right_halo = halos[n]
            for h in halos[n + 1:]:
                right_halo = right_halo + h
            left_halo = left_halo.reshape(rows, 1)
            right_halo = right_halo.reshape(rows, 1)
            i = lax.axis_index(ax)
            left_halo = jnp.where(i == 0, jnp.zeros_like(left_halo),
                                  left_halo)
            right_halo = jnp.where(i == n - 1, jnp.zeros_like(right_halo),
                                   right_halo)
            ext = jnp.concatenate([left_halo, u_, right_halo], axis=1)
            up = jnp.pad(ext[:-1, :], ((1, 0), (0, 0)))
            down = jnp.pad(ext[1:, :], ((0, 1), (0, 0)))
            return 0.25 * (ext[:, :-2] + ext[:, 2:] + up[:, 1:-1]
                           + down[:, 1:-1])

        from repro.comm.capture import BufferSpec
        out = cap.kernel(sweep, u, *recvs, name="jacobi_sweep",
                         out=BufferSpec((rows, cols), str(jnp.dtype(dtype))),
                         flops=5 * rows * cols)
        return out

    return session.capture(build, schedule=schedule)


def jacobi_step(u: jax.Array, axis_name: str, *, multipath: bool = False,
                use_kernel: bool = False) -> jax.Array:
    """One Jacobi sweep on a column-partitioned 2-D domain.

    ``u`` is the local block ``(rows, cols)`` of a domain decomposed along
    columns across the ring. Boundary columns are exchanged (optionally
    multi-path), then the 5-point stencil averages the four neighbours with
    zero (Dirichlet) conditions at the global domain edge — matching the
    NVIDIA multi-GPU Jacobi reference the paper benchmarks.

    With ``use_kernel`` the Pallas kernel reads ``u`` in place beside the
    two halo columns; the ``jnp`` update sweeps the halo-extended block.

    Each part runs under a ``jax.named_scope`` that the profiler shows in
    its ops' ``tf_op`` path: ``jacobi.halo`` (the exchange),
    ``jacobi.edges`` (the Dirichlet zeros), ``jacobi.extend`` (the
    halo-extended block, ``jnp`` path only) and ``jacobi.stencil`` (the
    update). The names are metadata only: the compiled program is the same
    without them.
    """
    with jax.named_scope("jacobi.halo"):
        left_halo, right_halo = halo_exchange_ring(
            u[:, :1], u[:, -1:], axis_name, multipath=multipath)

    with jax.named_scope("jacobi.edges"):
        n = axis_size(axis_name)
        i = lax.axis_index(axis_name)
        # global edge → Dirichlet zeros
        left_halo = jnp.where(i == 0, jnp.zeros_like(left_halo), left_halo)
        right_halo = jnp.where(i == n - 1, jnp.zeros_like(right_halo),
                               right_halo)

    if use_kernel:
        from repro.kernels.jacobi import ops as jacobi_ops
        with jax.named_scope("jacobi.stencil"):
            return jacobi_ops.jacobi_sweep(u, left_halo, right_halo)

    with jax.named_scope("jacobi.extend"):
        ext = jnp.concatenate([left_halo, u, right_halo], axis=1)
    with jax.named_scope("jacobi.stencil"):
        up = jnp.pad(ext[:-1, :], ((1, 0), (0, 0)))
        down = jnp.pad(ext[1:, :], ((0, 1), (0, 0)))
        return 0.25 * (ext[:, :-2] + ext[:, 2:] + up[:, 1:-1]
                       + down[:, 1:-1])
