"""Train-step builder: loss → grads → AdamW, with grad accumulation.

Produces jit-able step functions with explicit in/out shardings (the same
artifacts the multi-pod dry-run lowers). Gradient accumulation runs the
microbatch loop as a ``lax.scan`` so the HLO stays one-microbatch-sized.

Two communication modes:

* :func:`make_train_step` — auto-sharded: XLA inserts the collectives.
* :func:`make_dp_train_step` — manual data parallelism driven through a
  :class:`repro.comm.CommSession`: the step runs under ``shard_map`` over
  the session's axis and gradients are averaged with the session's
  multipath (bidirectional-ring) collectives instead of ``lax.psum``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import TYPE_CHECKING, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map
from repro.configs.base import ArchConfig
from repro.models import transformer as tfm
from repro.optim import OptimConfig, apply_updates, init_opt_state
from repro.training import sharding as shd

if TYPE_CHECKING:  # pragma: no cover - typing only
    from repro.comm.session import CommSession


@dataclasses.dataclass(frozen=True)
class TrainStepConfig:
    microbatches: int = 1          # gradient accumulation factor
    aux_coef: float = 0.01


def make_loss_fn(cfg: ArchConfig, ts: TrainStepConfig):
    def loss(params, batch):
        return tfm.loss_fn(params, cfg, batch, aux_coef=ts.aux_coef)
    return loss


def _make_grad_fn(cfg: ArchConfig, ts: TrainStepConfig) -> Callable:
    """``(params, batch) -> (loss, grads)`` with microbatch accumulation."""
    loss_fn = make_loss_fn(cfg, ts)
    grad_fn = jax.value_and_grad(loss_fn)

    def grads_of(params, batch):
        if ts.microbatches == 1:
            return grad_fn(params, batch)

        def split(x):
            b = x.shape[0]
            mb = b // ts.microbatches
            return x.reshape(ts.microbatches, mb, *x.shape[1:])
        micro = jax.tree.map(split, batch)
        zeros = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)

        def accum(carry, mb):
            acc, loss_acc = carry
            loss, grads = grad_fn(params, mb)
            acc = jax.tree.map(
                lambda a, g: a + g.astype(jnp.float32), acc, grads)
            return (acc, loss_acc + loss), None

        (gsum, lsum), _ = jax.lax.scan(accum, (zeros, 0.0), micro)
        grads = jax.tree.map(lambda g: g / ts.microbatches, gsum)
        return lsum / ts.microbatches, grads

    return grads_of


def make_train_step(cfg: ArchConfig, ts: TrainStepConfig,
                    opt: OptimConfig) -> Callable:
    """Returns ``step(state, batch) -> (state, metrics)`` (un-jitted).

    ``state = {"params": ..., "opt": ...}``. With ``ts.microbatches > 1``
    the batch's leading dim is split and gradients are accumulated in fp32
    via lax.scan (one-microbatch HLO).
    """
    grads_of = _make_grad_fn(cfg, ts)

    def step(state, batch):
        params = state["params"]
        loss, grads = grads_of(params, batch)
        new_params, new_opt, metrics = apply_updates(
            params, grads, state["opt"], opt)
        metrics["loss"] = loss
        return {"params": new_params, "opt": new_opt}, metrics

    return step


def make_dp_train_step(cfg: ArchConfig, ts: TrainStepConfig,
                       opt: OptimConfig, comm: "CommSession") -> Callable:
    """Data-parallel step with manual multipath gradient collectives.

    The returned ``step(state, batch) -> (state, metrics)`` runs under
    ``shard_map`` over ``comm``'s mesh axis: params/opt state are
    replicated, the batch is sharded on its leading dim, and per-shard
    gradients (and the loss) are averaged with
    ``comm.collectives.pmean`` — the bidirectional-ring all-reduce that
    stripes every hop across both ring directions. Numerically equivalent
    to ``make_train_step`` under jit (mean-of-shard-means == global mean
    for equal shards).
    """
    grads_of = _make_grad_fn(cfg, ts)
    ax = comm.axis_name
    mesh = comm.mesh

    def local_step(state, batch):
        params = state["params"]
        loss, grads = grads_of(params, batch)
        grads = jax.tree.map(comm.collectives.pmean, grads)
        loss = comm.collectives.pmean(loss)
        new_params, new_opt, metrics = apply_updates(
            params, grads, state["opt"], opt)
        metrics["loss"] = loss
        return {"params": new_params, "opt": new_opt}, metrics

    return shard_map(local_step, mesh=mesh,
                     in_specs=(P(), P(ax)), out_specs=(P(), P()),
                     check_vma=False)


def make_captured_dp_train_step(cfg: ArchConfig, ts: TrainStepConfig,
                                opt: OptimConfig, comm: "CommSession",
                                state, batch, *,
                                schedule: str | None = None,
                                max_paths: int | None = None,
                                num_chunks: int | None = None) -> Callable:
    """Data-parallel step captured as ONE heterogeneous graph —
    grad compute, multipath ring all-reduce, and the optimizer update
    all inside a single compiled launch (``session.capture``).

    ``state``/``batch`` are example pytrees (concrete or abstract) fixing
    the shapes; the returned ``step(state, batch) -> (state, metrics)``
    matches :func:`make_dp_train_step` to numerical tolerance (the
    captured all-reduce sums in fp32 ring order, the eager path in
    bidirectional-ring order). Every call is ONE engine dispatch — grad
    kernel, ``n-1`` exchange rounds, combine kernels, and the update
    kernel are nodes of one scheduled transfer graph, so
    ``comm.stats()["dispatches"]`` increments by one per step.
    """
    import math

    from repro.comm.capture import captured_psum

    grads_of = _make_grad_fn(cfg, ts)
    n = comm.engine.num_devices
    params_leaves, params_def = jax.tree.flatten(state["params"])
    opt_leaves, opt_def = jax.tree.flatten(state["opt"])
    batch_leaves, batch_def = jax.tree.flatten(batch)
    npar, nopt = len(params_leaves), len(opt_leaves)
    for b in batch_leaves:
        if b.shape[0] % n:
            raise ValueError(f"global batch dim {b.shape[0]} not divisible "
                             f"by {n} devices")
    grad_sizes = [math.prod(p.shape) for p in params_leaves]
    total = sum(grad_sizes)
    m_shapes = jax.eval_shape(lambda p, g, s: apply_updates(p, g, s, opt)[2],
                              state["params"], state["params"],
                              state["opt"])
    metric_keys = tuple(sorted(m_shapes)) + ("loss",)

    def build(cap):
        p_refs = [cap.input(tuple(p.shape), p.dtype, replicated=True)
                  for p in params_leaves]
        o_refs = [cap.input(tuple(o.shape), o.dtype, replicated=True)
                  for o in opt_leaves]
        b_refs = [cap.input((b.shape[0] // n,) + tuple(b.shape[1:]),
                            b.dtype) for b in batch_leaves]

        def grad_kernel(*leaves):
            params = params_def.unflatten(list(leaves[:npar]))
            bt = batch_def.unflatten(list(leaves[npar:]))
            loss, grads = grads_of(params, bt)
            flat = [g.astype(jnp.float32).ravel()
                    for g in params_def.flatten_up_to(grads)]
            return jnp.concatenate(
                flat + [loss.astype(jnp.float32).reshape(1)])

        gvec = cap.kernel(grad_kernel, *p_refs, *b_refs, name="grad",
                          flops=6 * total)
        tot = captured_psum(cap, gvec, n, max_paths=max_paths,
                            num_chunks=num_chunks, name="gradsum")

        def update_kernel(tot_v, *leaves):
            params = params_def.unflatten(list(leaves[:npar]))
            opt_state = opt_def.unflatten(list(leaves[npar:]))
            mean = tot_v / n
            gleaves, off = [], 0
            for p, sz in zip(params_leaves, grad_sizes):
                gleaves.append(mean[off:off + sz].reshape(p.shape)
                               .astype(p.dtype))
                off += sz
            loss = mean[total]
            grads = params_def.unflatten(gleaves)
            new_params, new_opt, metrics = apply_updates(
                params, grads, opt_state, opt)
            metrics["loss"] = loss
            mvec = jnp.stack([metrics[k].astype(jnp.float32)
                              for k in metric_keys])
            return (tuple(jax.tree.leaves(new_params))
                    + tuple(jax.tree.leaves(new_opt)) + (mvec,))

        return cap.kernel(update_kernel, tot, *p_refs, *o_refs,
                          name="update", flops=10 * total)

    captured = comm.capture(build, schedule=schedule)

    def step(st, bt):
        p_l = params_def.flatten_up_to(st["params"])
        o_l = opt_def.flatten_up_to(st["opt"])
        b_l = [jnp.asarray(x).reshape((n, x.shape[0] // n) + x.shape[1:])
               for x in batch_def.flatten_up_to(bt)]
        outs = captured(*p_l, *o_l, *b_l)
        outs0 = [o[0] for o in outs]   # replicated results: rows identical
        new_params = params_def.unflatten(outs0[:npar])
        new_opt = opt_def.unflatten(outs0[npar:npar + nopt])
        mvec = outs0[-1]
        metrics = {k: mvec[i] for i, k in enumerate(metric_keys)}
        return {"params": new_params, "opt": new_opt}, metrics

    return step


def state_shapes(cfg: ArchConfig, opt: OptimConfig):
    p = tfm.param_shapes(cfg)
    o = jax.eval_shape(lambda pp: init_opt_state(pp, opt), p)
    return {"params": p, "opt": o}


def state_shardings(cfg: ArchConfig, mesh: Mesh, opt: OptimConfig):
    abstract = state_shapes(cfg, opt)
    p_specs = shd.param_specs(cfg, mesh, abstract["params"])
    o_specs = shd.opt_state_specs(cfg, mesh, abstract["opt"], p_specs)
    return {
        "params": shd.to_shardings(mesh, p_specs),
        "opt": shd.to_shardings(mesh, o_specs),
    }, abstract


def init_state(cfg: ArchConfig, opt: OptimConfig, mesh: Mesh | None = None,
               seed: int = 0):
    """Materialize a sharded train state (smoke/e2e scale only)."""
    params = tfm.init_params(jax.random.key(seed), cfg)
    state = {"params": params, "opt": init_opt_state(params, opt)}
    if mesh is not None:
        shardings, _ = state_shardings(cfg, mesh, opt)
        state = jax.device_put(state, shardings)
    return state
