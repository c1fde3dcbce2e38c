"""Jit'd wrapper for the flash attention kernel.

On the CPU backend the kernel runs in Pallas interpret mode; elsewhere it
runs compiled. There is no fallback to the reference: shapes the kernel
cannot tile (on TPU the last two block dims must be divisible by 8 and
128, so a single-query ``(B, H, 1, D)`` q is refused) raise.
"""

from __future__ import annotations

import functools

import jax

from repro.kernels.flash_attention.kernel import flash_attention_kernel


def _is_cpu() -> bool:
    return jax.default_backend() == "cpu"


@functools.partial(jax.jit, static_argnames=("causal", "window", "scale",
                                             "block_q", "block_k",
                                             "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int | None = None,
                    scale: float | None = None, block_q: int = 128,
                    block_k: int = 128,
                    interpret: bool | None = None) -> jax.Array:
    if interpret is None:
        interpret = _is_cpu()
    return flash_attention_kernel(
        q, k, v, causal=causal, window=window, scale=scale,
        block_q=block_q, block_k=block_k, interpret=interpret)


def attention_flops(q_shape, k_shape) -> int:
    """Nominal FLOP count of one attention call: ``2·B·H·Sq·Sk·D`` for
    QKᵀ plus the same again for the value matmul."""
    b, h, sq, d = q_shape
    sk = k_shape[2]
    return 4 * b * h * sq * sk * d


def captured_flash_attention(cap, q, k, v, *, name: str = "flash_attention",
                             causal: bool = True, window: int | None = None,
                             scale: float | None = None,
                             telemetry=None, interpret: bool | None = None):
    """Record a flash-attention invocation on a ``session.capture`` step.

    ``cap`` is the :class:`~repro.comm.capture.StepCapture`; ``q``/``k``/
    ``v`` are capture refs with local shapes ``(B, H, S, D)``. Returns
    the attention output ref (q's shape). The node is priced for the
    lane model: ``flops`` from :func:`attention_flops`, and — when a
    :class:`~repro.comm.telemetry.TimelineRecorder` is passed as
    ``telemetry`` — ``cost_ns`` stamped from its recorded median for
    ``name``, so the overlap scheduler optimizes against measured
    kernel time. ``name`` is the capture's kernel identity: one adopter
    call per name per capture.
    """
    from repro.comm.capture import BufferSpec
    q_spec = cap.buffers[cap._resolve(q)]
    k_spec = cap.buffers[cap._resolve(k)]

    def attn(q_, k_, v_):
        return flash_attention(q_, k_, v_, causal=causal, window=window,
                               scale=scale, interpret=interpret)

    cost = int(telemetry.kernel_cost_ns(name)) if telemetry is not None \
        else 0
    return cap.kernel(attn, q, k, v, name=name,
                      out=BufferSpec(q_spec.shape, q_spec.dtype),
                      flops=attention_flops(q_spec.shape, k_spec.shape),
                      cost_ns=cost)
