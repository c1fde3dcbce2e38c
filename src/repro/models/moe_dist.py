"""Distributed MoE: explicit shard_map expert parallelism.

GSPMD cannot partition the capacity-dispatch scatter across (data × model)
without replicating terabytes (measured on kimi-k2: 857 GiB/device, 1.1e14
collective wire bytes). This module takes manual control:

* tokens enter replicated across the model axis (the natural state at the
  Megatron-SP boundary: the (B·S) dim is data-sharded, model-replicated),
* **dispatch is communication-free**: every model rank selects, sorts, and
  scatters only the tokens routed to ITS experts (EP) — or all tokens into
  its ff-shard (expert-TP fallback when E < model size),
* expert GEMMs run on local shards,
* **combine is one psum over the model axis** of the (T_local, d) output —
  each token's k expert contributions live on ≤k ranks, everyone else adds
  zeros. The psum also merges expert-TP partial sums for free.

Per-layer collective bytes drop from O(buffer × replication) to exactly one
(T_local × d) all-reduce — the same wire cost as a Megatron TP MLP.

The pure-jnp fallback (``repro.models.moe``) remains the reference; the two
paths agree to float tolerance (``tests/test_moe_dist.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from jax import shard_map

from repro.models import pspec


def _mesh_info():
    mesh = pspec._ambient_mesh()
    if mesh is None:
        return None
    shape = dict(mesh.shape)
    model = shape.get("model", 1)
    if model <= 1:
        return None
    dp = tuple(a for a in ("pod", "data") if a in shape)
    return mesh, dp, model


def _local_moe(x, router, w1, w3, w2, *, top_k: int, kind: str,
               capacity: int, num_experts: int, model_size: int,
               ep: bool, fsdp: bool, dp_axes: tuple):
    """Per-device body. x: (Tl, d) local tokens (replicated over model)."""
    tl, d = x.shape
    e = num_experts

    # -- FSDP weight gathering (ZeRO-3 all-gather before use) -------------
    if fsdp and dp_axes:
        ax = dp_axes[-1]  # "data"
        w1 = lax.all_gather(w1, ax, axis=1, tiled=True)
        w2 = lax.all_gather(w2, ax, axis=2, tiled=True)
        if w3 is not None:
            w3 = lax.all_gather(w3, ax, axis=1, tiled=True)

    # -- routing (identical on every model rank) ---------------------------
    logits = x.astype(jnp.float32) @ router
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = lax.top_k(probs, top_k)          # (Tl, k)
    gate_vals = gate_vals / jnp.sum(gate_vals, -1, keepdims=True)

    flat_ids = expert_ids.reshape(-1)                        # (Tl*k,)
    order = jnp.argsort(flat_ids)
    sorted_ids = flat_ids[order]
    token_of = order // top_k
    seg_start = jnp.searchsorted(sorted_ids, jnp.arange(e), side="left")
    pos = jnp.arange(tl * top_k) - seg_start[sorted_ids]
    keep = pos < capacity

    r = lax.axis_index("model")
    if ep:
        el = e // model_size
        e0 = r * el
        mine = keep & (sorted_ids >= e0) & (sorted_ids < e0 + el)
        local_e = jnp.where(mine, sorted_ids - e0, el)       # OOB ⇒ drop
        n_buf = el
    else:
        mine = keep
        local_e = jnp.where(mine, sorted_ids, e)
        n_buf = e
    safe_pos = jnp.where(mine, pos, capacity)

    buf = jnp.zeros((n_buf, capacity, d), x.dtype)
    buf = buf.at[local_e, safe_pos].set(x[token_of], mode="drop")

    h = jnp.einsum("ecd,edf->ecf", buf, w1, optimize=True)
    if kind in ("swiglu", "geglu"):
        u = jnp.einsum("ecd,edf->ecf", buf, w3, optimize=True)
        act = jax.nn.silu(h) if kind == "swiglu" else jax.nn.gelu(h)
        h = act * u
    elif kind == "relu2":
        h = jnp.square(jax.nn.relu(h))
    else:
        h = jax.nn.gelu(h)
    y = jnp.einsum("ecf,efd->ecd", h, w2, optimize=True)

    back = y.at[local_e, safe_pos].get(mode="fill", fill_value=0)
    weights = gate_vals.reshape(-1)[order] * mine
    out = jnp.zeros_like(x).at[token_of].add(
        (back * weights[:, None]).astype(x.dtype))
    # combine: sums each token's k expert contributions across their owner
    # ranks (EP) and/or the ff-shard partial sums (expert-TP).
    return lax.psum(out, "model")


def moe_apply_dist(x: jax.Array, params: dict, *, top_k: int, kind: str,
                   capacity_factor: float = 1.25, dropless: bool = False,
                   fsdp: bool = False):
    """shard_map MoE. x: (T, d) → (out, aux). Falls back to None when no
    model-parallel mesh is ambient (caller uses the pure-jnp path)."""
    info = _mesh_info()
    if info is None:
        return None
    mesh, dp, model = info
    t, d = x.shape
    e = params["router"].shape[-1]
    ndp = 1
    for a in dp:
        ndp *= dict(mesh.shape)[a]
    if t % max(1, ndp):
        return None
    tl = t // max(1, ndp)
    capacity = tl if dropless else max(
        1, int(tl * top_k / e * capacity_factor))
    ep = e % model == 0

    # aux loss from a (cheap) replicated routing pass outside the region
    logits = (x.astype(jnp.float32) @ params["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    _, expert_ids = lax.top_k(probs, top_k)
    density = jnp.mean(
        jax.nn.one_hot(expert_ids, e, dtype=jnp.float32).sum(1), 0)
    aux = e * jnp.sum(density * jnp.mean(probs, 0))

    fsdp = fsdp and "data" in dict(mesh.shape)
    w3 = params.get("w3")
    fs = "data" if fsdp else None
    w_spec = (P("model", fs, None) if ep else P(None, fs, "model"))
    w2_spec = (P("model", None, fs) if ep else P(None, "model", fs))

    body = functools.partial(
        _local_moe, top_k=top_k, kind=kind, capacity=capacity,
        num_experts=e, model_size=model, ep=ep, fsdp=fsdp, dp_axes=dp)

    def wrapped(xl, router, w1, w3_, w2):
        return body(xl, router, w1, w3_, w2)

    in_specs = (P(dp, None), P(None, None), w_spec,
                (w_spec if w3 is not None else P(None, None, None)),
                w2_spec)
    fn = shard_map(wrapped, mesh=mesh, in_specs=in_specs,
                   out_specs=P(dp, None), check_vma=False)
    if w3 is None:
        w3 = jnp.zeros((e, 1, 1), x.dtype)  # placeholder, unused by kinds
    out = fn(x, params["router"], params["w1"], w3, params["w2"])
    return out, aux
