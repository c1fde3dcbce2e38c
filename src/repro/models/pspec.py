"""Activation sharding constraints that degrade gracefully without a mesh.

GSPMD sharding propagation alone is not reliable through scanned layer
bodies — without anchors it happily re-shards activations from batch-split
to head-split (observed: 218 GiB/device temp on llama3-8b train). These
helpers pin the standard megatron-style activation layout:

* batch dims → (pod, data)
* head / hidden (TP) dims → model
* everything else replicated

``constrain`` is a no-op when no mesh is ambient (unit tests, single-CPU
smoke runs) and silently drops axes that do not divide (smollm's 15 heads).
"""

from __future__ import annotations

import jax
from jax.sharding import PartitionSpec as P

from jax.sharding import get_abstract_mesh

DP = ("pod", "data")   # logical batch axes (filtered per ambient mesh)


def _ambient_mesh():
    try:
        m = get_abstract_mesh()
    except Exception:  # noqa: BLE001
        return None
    if m is None or not getattr(m, "axis_names", ()):
        return None
    return m


def _safe(shape, spec, mesh) -> P:
    out = []
    for i, entry in enumerate(spec):
        if entry is None:
            out.append(None)
            continue
        axes = (entry,) if isinstance(entry, str) else tuple(entry)
        axes = [a for a in axes if a in mesh.axis_names]
        keep = []
        size = shape[i]
        for a in axes:
            n = mesh.shape[a]
            if n > 1 and size % n == 0:
                keep.append(a)
                size //= n
        out.append(tuple(keep) if len(keep) > 1 else
                   (keep[0] if keep else None))
    return P(*out)


def constrain(x: jax.Array, *entries) -> jax.Array:
    """with_sharding_constraint(x, P(*entries)) with fallback semantics."""
    mesh = _ambient_mesh()
    if mesh is None:
        return x
    entries = list(entries) + [None] * (x.ndim - len(entries))
    spec = _safe(x.shape, P(*entries[:x.ndim]), mesh)
    try:
        return jax.lax.with_sharding_constraint(x, spec)
    except Exception:  # noqa: BLE001 — no mesh context at trace time
        return x


def batch_first(x: jax.Array) -> jax.Array:
    """(B, ...) → batch over DP, rest replicated."""
    return constrain(x, DP)


def batch_heads(x: jax.Array) -> jax.Array:
    """(B, H, ...) → batch over DP, heads over model."""
    return constrain(x, DP, "model")


def batch_seq_heads(x: jax.Array) -> jax.Array:
    """(B, S, H, hd) or (B, H, S, hd): batch over DP, dim1... use explicit."""
    return constrain(x, DP, "model", None, None)


def batch_seq_hidden(x: jax.Array) -> jax.Array:
    """(B, S, ff): batch over DP, hidden over model (TP MLP)."""
    return constrain(x, DP, None, "model")


def hidden_last(x: jax.Array) -> jax.Array:
    """batch over DP on dim 0, TP on the last dim (MLP hidden)."""
    entries = [DP] + [None] * (x.ndim - 2) + ["model"]
    return constrain(x, *entries)


def seq_model(x: jax.Array) -> jax.Array:
    """(B, S, d): batch over DP, SEQUENCE over model (Megatron-SP layout).

    Used for the between-block residual stream: remat saves one carry per
    layer, and sequence-sharding it divides that stack by the model-axis
    size (llama3-8b train_4k: 16 GiB → 1 GiB/device).
    """
    return constrain(x, DP, "model", None)


def attn_qkv(x: jax.Array, role: str = "q") -> jax.Array:
    """(B, H, S, hd): heads over model when divisible. Fallbacks differ by
    role (§Perf iteration N1):

    * q (and k/v when q also can't head-shard): sequence over model —
      context parallelism (smollm's 15 / hymba's 25 heads),
    * k/v under GQA with head-sharded q: REPLICATE over model. Seq-sharding
      them against head-sharded q made the blockwise-attention scan
      re-gather every K/V block per step (nemotron: +TBs of all-gather);
      GQA k/v tensors are small — recomputing the projection everywhere is
      cheaper than any exchange.
    """
    mesh = _ambient_mesh()
    if mesh is None:
        return x
    model = dict(mesh.shape).get("model", 1)
    if x.shape[1] % model == 0:
        return constrain(x, DP, "model", None, None)
    if role == "kv":
        return constrain(x, DP, None, None, None)
    return constrain(x, DP, None, "model", None)


def moe_buf(x: jax.Array, num_experts: int) -> jax.Array:
    """(shards, E, C, d) expert capacity buffers: shard dim over DP always;
    E over model under EP, replicated under the expert-TP fallback
    (E < model-axis size — mixtral)."""
    mesh = _ambient_mesh()
    if mesh is None:
        return x
    model = dict(mesh.shape).get("model", 1)
    if num_experts % model == 0:
        return constrain(x, DP, "model", None, None)
    return constrain(x, DP, None, None, None)


def moe_hidden(x: jax.Array, num_experts: int) -> jax.Array:
    """(shards, E, C, ff): under expert-TP the hidden dim carries the model
    axis (per-expert megatron split); under EP it follows the E dim."""
    mesh = _ambient_mesh()
    if mesh is None:
        return x
    model = dict(mesh.shape).get("model", 1)
    if num_experts % model == 0:
        return constrain(x, DP, "model", None, None)
    return constrain(x, DP, None, None, "model")


def heads_shardable(num_heads: int) -> bool:
    """True when the q-head dim divides the ambient model axis."""
    mesh = _ambient_mesh()
    if mesh is None:
        return True
    model = dict(mesh.shape).get("model", 1)
    return num_heads % model == 0


def weight_gathered(w: jax.Array, tp_dim: int | None = None) -> jax.Array:
    """ZeRO-3 gather-before-use (§Perf iteration N3): FSDP-sharded weights
    flowing straight into a matmul make GSPMD bounce the ACTIVATIONS into
    d-sharded / batch-gathered layouts (nemotron: ~14 GB/layer of
    all-reduce + collective-permute on batch-replicated tensors). Gathering
    the weight to its TP-only layout first costs one weight-sized
    all-gather (0.7-2.7 GB/layer) instead.

    ``tp_dim`` is the dim that keeps the model axis (None = fully
    replicated).
    """
    entries = [None] * w.ndim
    if tp_dim is not None:
        entries[tp_dim] = "model"
    return constrain(w, *entries)
