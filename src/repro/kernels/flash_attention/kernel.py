"""Pallas TPU kernel: blockwise (flash) attention with GQA + sliding window.

Used by the framework's prefill path (32k contexts make materializing the
(S, S) score matrix infeasible: 32768² × 4B = 4 GiB per head). Canonical TPU
formulation:

* grid ``(batch, q_heads, q_blocks, kv_blocks)`` — the last dimension is
  sequential ("arbitrary"), carrying the online-softmax state in VMEM
  scratch across kv blocks,
* BlockSpecs tile Q/O as ``(1, 1, block_q, d)`` and K/V as
  ``(1, 1, block_k, d)``; the K/V index map folds the GQA group mapping
  (``kv_head = q_head // q_per_kv``) so grouped heads never materialize,
* block shapes default to 128×128: lane-dim and MXU-aligned,
* masking supports causal, sliding-window (Mistral/Gemma-style), and the
  sequence-padding tail in one predicate; masked probabilities are zeroed
  explicitly so fully-masked rows stay exact zeros (guarded normalization).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, window: int | None,
                  seq_len: int, block_q: int, block_k: int,
                  num_kv_blocks: int):
    i = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)             # (bq, d)
    k = k_ref[0, 0].astype(jnp.float32)             # (bk, d)
    v = v_ref[0, 0].astype(jnp.float32)

    s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32) * scale

    row = i * block_q + lax.broadcasted_iota(jnp.int32,
                                             (block_q, block_k), 0)
    col = j * block_k + lax.broadcasted_iota(jnp.int32,
                                             (block_q, block_k), 1)
    mask = col < seq_len
    if causal:
        mask &= col <= row
    if window is not None:
        mask &= col > row - window

    s = jnp.where(mask, s, NEG_INF)
    m_prev = m_scr[:, :1]                            # (bq, 1)
    l_prev = l_scr[:, :1]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new) * mask                    # zero masked lanes
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(j == num_kv_blocks - 1)
    def _finalize():
        l = l_scr[:, :1]
        o_ref[0, 0] = (acc_scr[...] /
                       jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def flash_attention_kernel(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool = True, window: int | None = None,
                           scale: float | None = None, block_q: int = 128,
                           block_k: int = 128,
                           interpret: bool = True) -> jax.Array:
    """q: (B, Hq, S, D); k, v: (B, Hkv, S, D) with Hq % Hkv == 0."""
    b, hq, s, d = q.shape
    hkv = k.shape[1]
    if hq % hkv:
        raise ValueError(f"Hq={hq} not a multiple of Hkv={hkv}")
    qpk = hq // hkv
    if scale is None:
        scale = d ** -0.5

    block_q = min(block_q, s)
    block_k = min(block_k, s)
    pad_q = (-s) % block_q
    pad_k = (-s) % block_k
    if pad_q or pad_k:
        qp = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
        kp = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        vp = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    else:
        qp, kp, vp = q, k, v
    sq, sk = s + pad_q, s + pad_k
    nq, nk = sq // block_q, sk // block_k

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        seq_len=s, block_q=block_q, block_k=block_k, num_kv_blocks=nk)

    out = pl.pallas_call(
        kernel,
        grid=(b, hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h, i, j: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h, i, j, qpk=qpk: (b_, h // qpk, j, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h, i, j, qpk=qpk: (b_, h // qpk, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda b_, h, i, j: (b_, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),   # running max
            pltpu.VMEM((block_q, 128), jnp.float32),   # running sum
            pltpu.VMEM((block_q, d), jnp.float32),     # accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(qp, kp, vp)
    return out[:, :, :s, :]
