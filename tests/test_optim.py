"""Optimizer + gradient compression tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from jax import shard_map

from repro.optim import (OptimConfig, apply_updates, compressed_psum,
                         compressed_psum_with_feedback, global_norm,
                         init_opt_state, lr_schedule)


def _train_quadratic(moment_dtype, steps=120):
    cfg = OptimConfig(learning_rate=0.1, warmup_steps=5, total_steps=steps,
                      weight_decay=0.0, moment_dtype=moment_dtype)
    target = jnp.asarray(np.random.RandomState(0).randn(32), jnp.float32)
    params = {"w": jnp.zeros((32,), jnp.float32)}
    state = init_opt_state(params, cfg)

    def loss(p):
        return jnp.sum(jnp.square(p["w"] - target))

    for _ in range(steps):
        grads = jax.grad(loss)(params)
        params, state, _ = apply_updates(params, grads, state, cfg)
    return float(loss(params))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "int8"])
def test_adamw_converges_all_moment_dtypes(dtype):
    assert _train_quadratic(dtype) < 1e-2


def test_lr_schedule_shape():
    cfg = OptimConfig(learning_rate=1.0, warmup_steps=10, total_steps=100,
                      min_lr_ratio=0.1)
    lrs = [float(lr_schedule(cfg, jnp.int32(s))) for s in range(101)]
    assert lrs[0] < lrs[9] <= 1.0        # warmup
    assert abs(lrs[10] - 1.0) < 0.02     # peak
    assert abs(lrs[100] - 0.1) < 0.02    # cosine floor


def test_global_norm():
    tree = {"a": jnp.asarray([3.0]), "b": jnp.asarray([4.0])}
    assert float(global_norm(tree)) == pytest.approx(5.0)


def test_grad_clipping_applied():
    cfg = OptimConfig(learning_rate=1e-3, clip_norm=1.0, warmup_steps=0,
                      total_steps=10, weight_decay=0.0)
    params = {"w": jnp.zeros((4,), jnp.float32)}
    state = init_opt_state(params, cfg)
    huge = {"w": jnp.full((4,), 1e6, jnp.float32)}
    new_params, _, metrics = apply_updates(params, huge, state, cfg)
    assert float(metrics["grad_norm"]) > 1e5
    assert float(jnp.max(jnp.abs(new_params["w"]))) < 1e-2


def test_int8_moments_zero_size_leaf():
    cfg = OptimConfig(moment_dtype="int8")
    params = {"w": jnp.zeros((0, 4), jnp.float32)}
    state = init_opt_state(params, cfg)
    new_p, state, _ = apply_updates(params, params, state, cfg)
    assert new_p["w"].shape == (0, 4)


def test_compressed_psum_error_bound(dev_mesh):
    x = jnp.asarray(np.random.RandomState(1).randn(8, 256), jnp.float32)

    def body(v):
        return compressed_psum(v[0], "dev")[None]

    got = jax.jit(shard_map(body, mesh=dev_mesh, in_specs=P("dev"),
                            out_specs=P("dev"),
                            check_vma=False))(x)
    ref = np.mean(np.asarray(x), axis=0)
    rel = np.max(np.abs(np.asarray(got)[0] - ref)) / (
        np.max(np.abs(ref)) + 1e-9)
    assert rel < 0.02   # int8 quantization error bound


def test_error_feedback_reduces_bias(dev_mesh):
    """Residual carrying keeps the multi-step mean error near zero."""
    rng = np.random.RandomState(2)
    steps = 30
    g = jnp.asarray(rng.randn(8, 128), jnp.float32) * 0.1

    def run(with_feedback):
        res = jnp.zeros((8, 128), jnp.float32)
        acc = jnp.zeros((128,), jnp.float32)
        for _ in range(steps):
            if with_feedback:
                def body(v, r):
                    out, nr = compressed_psum_with_feedback(
                        v[0], r[0], "dev")
                    return out[None], nr[None]
                out, res = jax.jit(shard_map(
                    body, mesh=dev_mesh, in_specs=(P("dev"), P("dev")),
                    out_specs=(P("dev"), P("dev")),
                    check_vma=False))(g, res)
                acc = acc + out[0]
            else:
                def body(v):
                    return compressed_psum(v[0], "dev")[None]
                out = jax.jit(shard_map(
                    body, mesh=dev_mesh, in_specs=P("dev"),
                    out_specs=P("dev"), check_vma=False))(g)
                acc = acc + out[0]
        true = np.mean(np.asarray(g), 0) * steps
        return np.max(np.abs(np.asarray(acc) - true))

    assert run(True) <= run(False) + 1e-5
