"""Pallas TPU kernel: chunked RWKV-6 (Finch) gated linear recurrence.

Recurrence per head (state ``S: (dk, dv)``, data-dependent decay ``w_t``,
bonus ``u``):

    o_t = r_t · (S_{t-1} + diag(u) kᵀ_t v_t)
    S_t = diag(w_t) S_{t-1} + kᵀ_t v_t

The kernel processes the sequence in chunks of length ``L`` (grid dim
sequential, state carried in VMEM scratch) and converts the recurrence into
MXU matmuls via the standard chunked factorization: with per-channel
log-decay cumsums ``c_t = Σ_{s≤t} log w_s``,

    q̃_t = r_t ⊙ exp(c_{t-1})       (decay since chunk start)
    k̃_s = k_s ⊙ exp(−c_s)          (inverse decay to chunk start)
    o_t  = q̃_t S_prev  +  Σ_{s<t} (q̃_t·k̃_s) v_s  +  (r_t·(u⊙k_t)) v_t
    S'   = diag(exp(c_L)) S_prev + (k̃ ⊙ exp(c_L))ᵀ V

Numerical-range note: the q̃/k̃ split is exact but bounded by
``exp(±|Σ log w|)`` over one chunk; with the RWKV-6 parameterization
(w = exp(−exp(x)), practical decays ≥ 0.8) chunk 64 stays well inside fp32
range. The chunk length is a BlockSpec tunable.

Grid: ``(batch*heads, seq//L)``; blocks ``(1, L, d)`` for r/k/v/w and
``(1, dk)`` for the per-head bonus ``u``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu



def _rwkv6_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, s_scr, *,
                  chunk: int):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    r = r_ref[0].astype(jnp.float32)        # (L, dk)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)        # (L, dv)
    w = w_ref[0].astype(jnp.float32)        # (L, dk) decays in (0, 1]
    u = u_ref[0].astype(jnp.float32)        # (dk,)

    logw = jnp.log(w)
    cum = jnp.cumsum(logw, axis=0)          # c_t, inclusive
    cum_prev = cum - logw                   # c_{t-1}, exclusive

    qt = r * jnp.exp(cum_prev)              # q̃
    kt = k * jnp.exp(-cum)                  # k̃

    scores = lax.dot_general(qt, kt, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (L, L)
    row = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    scores = jnp.where(col < row, scores, 0.0)          # strictly causal

    bonus = jnp.sum(r * u[None, :] * k, axis=-1)        # (L,) diagonal term
    o = (lax.dot_general(scores, v, (((1,), (0,)), ((), ())),
                         preferred_element_type=jnp.float32)
         + bonus[:, None] * v
         + lax.dot_general(qt, s_scr[...], (((1,), (0,)), ((), ())),
                           preferred_element_type=jnp.float32))

    decay_l = jnp.exp(cum[-1])                           # (dk,)
    s_scr[...] = (s_scr[...] * decay_l[:, None]
                  + lax.dot_general(kt * decay_l[None, :], v,
                                    (((0,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32))
    o_ref[0] = o.astype(o_ref.dtype)


def rwkv6_scan_kernel(r: jax.Array, k: jax.Array, v: jax.Array,
                      w: jax.Array, u: jax.Array, *, chunk: int = 64,
                      interpret: bool = True) -> jax.Array:
    """r/k/w: (BH, S, dk); v: (BH, S, dv); u: (BH, dk). Returns (BH, S, dv).

    S must be a multiple of ``chunk`` (pad upstream; decays pad with 1.0).
    """
    bh, s, dk = r.shape
    dv = v.shape[-1]
    if s % chunk:
        raise ValueError(f"seq {s} not a multiple of chunk {chunk}")
    nchunks = s // chunk

    kernel = functools.partial(_rwkv6_kernel, chunk=chunk)
    seq_spec_k = pl.BlockSpec((1, chunk, dk), lambda h, t: (h, t, 0))
    seq_spec_v = pl.BlockSpec((1, chunk, dv), lambda h, t: (h, t, 0))
    return pl.pallas_call(
        kernel,
        grid=(bh, nchunks),
        in_specs=[seq_spec_k, seq_spec_k, seq_spec_v, seq_spec_k,
                  pl.BlockSpec((1, dk), lambda h, t: (h, 0))],
        out_specs=seq_spec_v,
        out_shape=jax.ShapeDtypeStruct((bh, s, dv), r.dtype),
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(r, k, v, w, u)
