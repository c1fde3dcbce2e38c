"""The yardstick's plain references and comparisons.

Nothing here imports the program under test. Data is made on the device from
the run's seed; the references are the plainest computations of the same
answers (a single-pair ``lax.ppermute`` for a send, the ``jax.numpy`` 5-point
sweep for Jacobi), and the comparisons reduce an answer and its reference to
one number that is held against a limit.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

#: Seeds are any whole number; JAX keys take 32 bits, so larger seeds are
#: folded into two words.
_SEED_MASK = (1 << 32) - 1


def key(seed: int) -> jax.Array:
    """A PRNG key for any non-negative whole ``seed``, also past 2**32."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    k = jax.random.key(seed & _SEED_MASK)
    return jax.random.fold_in(k, (seed >> 32) & _SEED_MASK)


def random_f32(seed: int, shape, sharding=None) -> jax.Array:
    """Seeded N(0, 1) f32 data made on the device in one jitted call."""
    fn = jax.jit(lambda k: jax.random.normal(k, shape, jnp.float32),
                 out_shardings=sharding)
    return fn(key(seed))


# -- sends --------------------------------------------------------------------

def stacked_messages(seed: int, mesh, axis: str, count: int,
                     nelems: int) -> jax.Array:
    """``(ndev, count, nelems)`` f32, one row per device of ``mesh``: row
    ``src`` holds the messages that device ``src`` sends."""
    ndev = mesh.devices.size
    return random_f32(seed, (ndev, count, nelems),
                      NamedSharding(mesh, P(axis)))


def local_row(stacked: jax.Array, device) -> jax.Array:
    """The part of a device-stacked array that ``device`` holds, committed
    to that device: a message where its sender keeps it."""
    (shard,) = [s for s in stacked.addressable_shards if s.device == device]
    return shard.data[0]


@functools.lru_cache(maxsize=8)
def _ppermute_fn(mesh, axis: str, pairs: tuple, dtype):
    def body(v):
        return jax.lax.ppermute(v.astype(dtype), axis, pairs).astype(
            v.dtype)
    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P(axis),
                                 out_specs=P(axis), check_vma=False))


def ppermute_reference(mesh, axis: str, stacked: jax.Array, pairs,
                       dtype=jnp.float32) -> jax.Array:
    """Plain single-pair ``lax.ppermute`` of a device-stacked array inside
    ``shard_map``, carried on the wire as ``dtype`` (f32: exact)."""
    fn = _ppermute_fn(mesh, axis, tuple(map(tuple, pairs)),
                      jnp.dtype(dtype))
    return fn(stacked)


def on_device(x: jax.Array, device) -> jax.Array | None:
    """The copy of ``x`` that ``device`` holds, or None if it holds none.
    A received message counts only where its destination holds it whole."""
    for s in x.addressable_shards:
        if s.device == device and s.data.shape == x.shape:
            return s.data
    return None


@jax.jit
def _bit_mismatches(a: jax.Array, b: jax.Array) -> jax.Array:
    ua = jax.lax.bitcast_convert_type(a, jnp.uint32)
    ub = jax.lax.bitcast_convert_type(b, jnp.uint32)
    return jnp.sum(ua != ub, dtype=jnp.int32)


def mismatched_elements(got: jax.Array | None, want: jax.Array) -> int:
    """f32 elements of ``got`` whose bits differ from ``want``; every
    element counts as wrong where nothing arrived or the shape differs."""
    if got is None or got.shape != want.shape or got.dtype != want.dtype:
        return int(want.size)
    got = jax.device_put(got, next(iter(want.devices())))
    return int(_bit_mismatches(got, want))


# -- Jacobi -------------------------------------------------------------------

def jacobi_sweep_ref(ext: jax.Array) -> jax.Array:
    """5-point Jacobi update of the interior of ``ext: (rows, W + 2)`` with
    Dirichlet-zero top and bottom boundaries."""
    c = ext[:, 1:-1]
    up = jnp.pad(c[:-1, :], ((1, 0), (0, 0)))
    down = jnp.pad(c[1:, :], ((0, 1), (0, 0)))
    return 0.25 * (ext[:, :-2] + ext[:, 2:] + up + down)


def jacobi_step_ref(u: jax.Array, dtype=jnp.float32) -> jax.Array:
    """One sweep of the whole domain ``u: (rows, W)`` with Dirichlet-zero
    edges on all four sides, computed in ``dtype`` (f32: the reference)."""
    v = u.astype(dtype)
    zero = jnp.zeros((v.shape[0], 1), dtype)
    return jacobi_sweep_ref(jnp.concatenate([zero, v, zero], axis=1)
                            ).astype(u.dtype)


@jax.jit
def _relative_gap(got: jax.Array, want: jax.Array) -> jax.Array:
    scale = jnp.maximum(jnp.max(jnp.abs(want)), jnp.finfo(want.dtype).tiny)
    gap = jnp.max(jnp.abs(got - want))
    return jnp.where(jnp.all(jnp.isfinite(got)), gap / scale, jnp.inf)


def relative_gap(got: jax.Array, want: jax.Array) -> float:
    """max |got - want| over max |want|; infinite where ``got`` is not
    finite or its shape differs."""
    if got.shape != want.shape:
        return float("inf")
    return float(_relative_gap(got, want))
