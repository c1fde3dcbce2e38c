"""Sharding-rule validity across all archs × production meshes + data
pipeline determinism + pipeline parallelism."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from jax import set_mesh
from jax.sharding import AbstractMesh

from repro.configs import REGISTRY, load_all
from repro.data import DataConfig, SyntheticDataset
from repro.models import transformer as tfm
from repro.optim import OptimConfig
from repro.training import sharding as shd

load_all()
ALL = sorted(REGISTRY)

SINGLE = AbstractMesh((16, 16), ("data", "model"))
MULTI = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def _check_specs(specs, shapes, mesh):
    flat_s, _ = jax.tree_util.tree_flatten(
        specs, is_leaf=lambda x: isinstance(x, P))
    flat_a, _ = jax.tree_util.tree_flatten(shapes)
    assert len(flat_s) == len(flat_a)
    for spec, leaf in zip(flat_s, flat_a):
        for i, entry in enumerate(spec):
            if entry is None:
                continue
            axes = (entry,) if isinstance(entry, str) else entry
            n = 1
            for a in axes:
                n *= mesh.shape[a]
            assert leaf.shape[i] % n == 0, (spec, leaf.shape)


@pytest.mark.parametrize("mesh", [SINGLE, MULTI], ids=["single", "multi"])
@pytest.mark.parametrize("name", ALL)
def test_param_specs_divisible(name, mesh):
    cfg = REGISTRY[name]
    abstract = tfm.param_shapes(cfg)
    specs = shd.param_specs(cfg, mesh, abstract)
    _check_specs(specs, abstract, mesh)


@pytest.mark.parametrize("name", ["llama3_8b", "kimi_k2_1t_a32b",
                                  "rwkv6_1_6b", "hymba_1_5b"])
def test_cache_specs_divisible(name):
    cfg = REGISTRY[name]
    spec = tfm.cache_spec(cfg, max_len=32768, kv_chunks=16)
    shapes = tfm.cache_shapes(cfg, 128, spec)
    specs = shd.cache_specs(cfg, SINGLE, shapes, 128)
    _check_specs(specs, shapes, SINGLE)


def test_tp_sharding_present_for_llama():
    cfg = REGISTRY["llama3_8b"]
    specs = shd.param_specs(cfg, SINGLE, tfm.param_shapes(cfg))
    wq = specs["layers"]["attn"]["wq"]
    assert "model" in jax.tree_util.tree_leaves(
        [wq], is_leaf=lambda x: isinstance(x, P))[0]
    assert specs["embed"][0] == "model"      # vocab sharded


def test_moe_ep_vs_tp_rule():
    kimi = REGISTRY["kimi_k2_1t_a32b"]       # 384 experts: EP
    mixtral = REGISTRY["mixtral_8x22b"]      # 8 experts: expert-TP
    sk = shd.param_specs(kimi, SINGLE, tfm.param_shapes(kimi))
    sm = shd.param_specs(mixtral, SINGLE, tfm.param_shapes(mixtral))
    assert sk["layers"]["moe"]["w1"][1] == "model"         # E sharded
    assert sm["layers"]["moe"]["w1"][1] is None            # E replicated
    assert sm["layers"]["moe"]["w1"][3] == "model"         # ff sharded


# ------------------------------- data --------------------------------------
def test_data_deterministic():
    cfg = REGISTRY["smollm_360m"].reduced()
    ds = SyntheticDataset(cfg, DataConfig(seq_len=16, global_batch=4,
                                          seed=3))
    a = ds.batch_at(5)
    b = ds.batch_at(5)
    c = ds.batch_at(6)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    assert not np.array_equal(a["tokens"], c["tokens"])
    # labels are next-token shifted
    np.testing.assert_array_equal(a["tokens"][:, 1:], a["labels"][:, :-1])


def test_audio_batch_shape():
    cfg = REGISTRY["hubert_xlarge"].reduced()
    ds = SyntheticDataset(cfg, DataConfig(seq_len=8, global_batch=2))
    b = ds.batch_at(0)
    assert b["features"].shape == (2, 8, cfg.frontend_dim)


def test_prefetch_loader_order():
    from repro.data import PrefetchLoader
    cfg = REGISTRY["smollm_360m"].reduced()
    ds = SyntheticDataset(cfg, DataConfig(seq_len=8, global_batch=2))
    loader = PrefetchLoader(ds, start_step=3, prefetch=2)
    steps = [next(loader)[0] for _ in range(4)]
    loader.close()
    assert steps == [3, 4, 5, 6]


# ------------------------- pipeline parallelism ----------------------------
def test_pipeline_parallel_matches_sequential():
    from repro.training.pipeline import pipeline_apply
    n_stages, m, mb, d = 4, 6, 3, 8
    mesh = jax.sharding.Mesh(
        np.array(jax.devices()[:n_stages]), ("pipe",))
    rng = np.random.RandomState(0)
    w = jnp.asarray(rng.randn(n_stages, d, d).astype(np.float32)) * 0.3
    x = jnp.asarray(rng.randn(m, mb, d).astype(np.float32))

    def stage_fn(wl, h):
        return jnp.tanh(h @ wl)

    ref = x
    for i in range(n_stages):
        ref = jnp.tanh(ref @ w[i])

    for multipath in (False, True):
        got = pipeline_apply(stage_fn, w, x, mesh, microbatches=m,
                             multipath=multipath)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=1e-5)


# ----------------------- sharded serve integration -------------------------
@pytest.mark.parametrize("name", ["llama3_8b", "rwkv6_1_6b"])
def test_decode_step_sharded_matches_unsharded(name, dp_tp_mesh):
    """decode_step under a (data=2, model=4) mesh with launcher cache
    shardings must be numerically identical to the single-device path."""
    import dataclasses
    from jax.sharding import NamedSharding
    cfg = dataclasses.replace(REGISTRY[name].reduced(), capacity_factor=8.0)
    params = tfm.init_params(jax.random.key(0), cfg)
    b, s = 4, 8
    toks = jax.random.randint(jax.random.key(1), (b, s), 0, cfg.vocab_size)
    spec = tfm.cache_spec(cfg, max_len=s, kv_chunks=4)
    # unsharded reference
    cache_ref = tfm.init_cache(cfg, b, spec)
    logits_ref = []
    for t in range(s):
        lg, cache_ref = tfm.decode_step(params, cfg, cache_ref,
                                        toks[:, t:t + 1], jnp.int32(t),
                                        spec)
        logits_ref.append(lg)
    # sharded run
    cache = tfm.init_cache(cfg, b, spec)
    c_specs = shd.cache_specs(cfg, dp_tp_mesh,
                              jax.eval_shape(lambda: cache), b)
    cache = jax.device_put(cache, jax.tree.map(
        lambda sp: NamedSharding(dp_tp_mesh, sp), c_specs,
        is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec)))
    with set_mesh(dp_tp_mesh):
        step = jax.jit(lambda c, t, i: tfm.decode_step(
            params, cfg, c, t, i, spec))
        for t in range(s):
            lg, cache = step(cache, toks[:, t:t + 1], jnp.int32(t))
            np.testing.assert_allclose(
                np.asarray(lg, np.float32),
                np.asarray(logits_ref[t], np.float32), atol=2e-3)


@pytest.mark.parametrize("name", ["llama3_8b", "mixtral_8x22b"])
def test_train_step_sharded_matches_unsharded(name, dp_tp_mesh):
    """One sharded train step (full launcher shardings) equals the
    single-device step to numerical tolerance."""
    import dataclasses
    from repro.optim import OptimConfig
    from repro.training import (TrainStepConfig, init_state,
                                make_train_step, state_shardings)
    cfg = dataclasses.replace(REGISTRY[name].reduced(), capacity_factor=8.0)
    opt = OptimConfig(learning_rate=1e-3, warmup_steps=1, total_steps=5)
    ds_batch = {
        "tokens": jax.random.randint(jax.random.key(2), (4, 16), 0,
                                     cfg.vocab_size),
        "labels": jax.random.randint(jax.random.key(3), (4, 16), 0,
                                     cfg.vocab_size),
        "mask": jnp.ones((4, 16), jnp.float32),
    }
    step = make_train_step(cfg, TrainStepConfig(), opt)
    s_ref, m_ref = jax.jit(step)(init_state(cfg, opt, seed=7), ds_batch)
    with set_mesh(dp_tp_mesh):
        state = init_state(cfg, opt, mesh=dp_tp_mesh, seed=7)
        s_got, m_got = jax.jit(step)(state, ds_batch)
    assert abs(float(m_got["loss"]) - float(m_ref["loss"])) < 2e-3
    for a, b in zip(jax.tree.leaves(s_ref["params"]),
                    jax.tree.leaves(s_got["params"])):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=5e-3)
