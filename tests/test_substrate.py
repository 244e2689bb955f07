"""Data pipeline, checkpointing, optim, fedavg/baselines, sharding rules."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.checkpoint import restore, save
from repro.configs import get_config
from repro.configs.base import FedZOConfig
from repro.core import baselines, fedavg
from repro.data.synthetic import (lm_batches, lm_token_stream,
                                  make_classification, noniid_shards,
                                  random_partition)
from repro.launch.sharding import leaf_spec
from repro.models.simple import softmax_init, softmax_loss


def test_noniid_shards_label_concentration():
    x, y = make_classification(4000, 16, 10, seed=0)
    clients = noniid_shards(x, y, 50)
    assert len(clients) == 50
    label_counts = [len(np.unique(c["y"])) for c in clients]
    assert max(label_counts) <= 4  # ≤ 2 shards × ≤ 2 boundary labels
    total = sum(len(c["y"]) for c in clients)
    assert total == 50 * (4000 // 100) * 2


def test_random_partition_uneven_sizes():
    x, y = make_classification(1000, 8, 10, seed=1)
    clients = random_partition(x, y, 10, seed=2)
    sizes = [len(c["y"]) for c in clients]
    assert sum(sizes) == 1000 and min(sizes) >= 1
    assert len(set(sizes)) > 1  # 'random number of samples' per device


def test_data_determinism():
    a = make_classification(100, 8, 4, seed=7)[0]
    b = make_classification(100, 8, 4, seed=7)[0]
    np.testing.assert_array_equal(a, b)
    t1 = lm_token_stream(500, 64, seed=3)
    t2 = lm_token_stream(500, 64, seed=3)
    np.testing.assert_array_equal(t1, t2)


def test_lm_batches_are_shifted():
    toks = lm_token_stream(2000, 32, seed=0)
    b = lm_batches(toks, 4, 16, np.random.default_rng(0))
    np.testing.assert_array_equal(b["tokens"][:, 1:], b["labels"][:, :-1])


def test_checkpoint_roundtrip(tmp_path):
    params = softmax_init(jax.random.key(0))
    params = jax.tree.map(
        lambda x: x + jax.random.normal(jax.random.key(1), x.shape), params)
    save(str(tmp_path / "ck"), params, step=7, meta=FedZOConfig())
    restored, step = restore(str(tmp_path / "ck"), params)
    assert step == 7
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_fedavg_round_descends():
    x, y = make_classification(2000, 784, 10, seed=0)
    clients = noniid_shards(x, y, 10)
    cfg = FedZOConfig(n_devices=10, n_participating=10, local_iters=5,
                      lr=0.01, b1=32)
    from repro.data.synthetic import sample_local_batches
    rng = np.random.default_rng(0)
    per = [sample_local_batches(c, rng, 5, 32) for c in clients]
    batches = jax.tree.map(lambda *xs: jnp.stack(xs), *per)
    params = softmax_init(jax.random.key(0))
    p2, m = fedavg.round_simulated(softmax_loss, params, batches, cfg)
    full = {"x": jnp.asarray(x), "y": jnp.asarray(y)}
    assert float(softmax_loss(p2, full)) < float(softmax_loss(params, full))


def test_zone_s_and_dzopa_descend_quadratic():
    def loss(params, batch):
        return 0.5 * jnp.sum((params["x"] - 1.0) ** 2)

    params = {"x": jnp.zeros((16,))}
    p, l0 = baselines.zone_s_round(loss, params, None, jax.random.key(0),
                                   rho=50.0, mu=1e-3, b2=8)
    assert float(loss(p, None)) < float(l0)

    cp = {"x": jnp.zeros((4, 16))}
    batches = jnp.zeros((4, 1))
    rngs = jax.random.split(jax.random.key(1), 4)
    cfg = FedZOConfig(lr=0.05, mu=1e-3, b2=8)
    cp2, l = baselines.dzopa_round(lambda p, b: loss(p, None), cp,
                                   batches, rngs, cfg)
    assert float(loss({"x": cp2["x"][0]}, None)) < float(l)
    # consensus: all agents equal after fully-connected mixing
    np.testing.assert_allclose(np.asarray(cp2["x"][0]),
                               np.asarray(cp2["x"][3]))


class _FakeMesh:
    shape = {"data": 16, "model": 16}
    axis_names = ("data", "model")


def test_leaf_spec_rules():
    mesh = _FakeMesh()
    # vocab-parallel embed
    assert tuple(leaf_spec("['embed']['tok']", (151936, 896), mesh)) == \
        ("model", None)
    # non-divisible vocab -> replicated
    assert tuple(leaf_spec("['embed']['tok']", (256206, 1024), mesh)) == \
        (None, None)
    # expert weights: E over model, ff over data
    spec = leaf_spec("['moe_blocks']['moe']['w_gate']", (58, 256, 7168, 2048),
                     mesh)
    assert tuple(spec) == (None, "model", None, "data")
    spec = leaf_spec("['moe_blocks']['moe']['w_down']", (58, 256, 2048, 7168),
                     mesh)
    assert tuple(spec) == (None, "model", "data", None)
    # stacked dense weight: layer dim never sharded
    spec = leaf_spec("['blocks']['mlp']['w_up']", (24, 896, 4864), mesh)
    assert spec[0] is None and "model" in tuple(spec)
    # awkward heads fall back (40 not divisible by 16): wq [d, 40*128]
    spec = leaf_spec("['blocks']['attn']['wq']", (64, 5120, 5120), mesh)
    assert tuple(spec)[1:] != (None, None)
    # tiny leaves replicated
    assert tuple(leaf_spec("['final_norm']['scale']", (896,), mesh)) == ()


def test_cosine_schedule_monotone_tail():
    from repro.optim.sgd import cosine_lr
    lrs = [float(cosine_lr(s, base_lr=1.0, total_steps=100, warmup=10))
           for s in range(0, 100, 10)]
    assert lrs[1] >= lrs[0] or lrs[0] < 1e-6  # warmup ramps
    assert lrs[-1] < lrs[2]


@pytest.mark.parametrize("env_set", [False, True])
def test_compile_cache_dir(monkeypatch, env_set):
    """Entry points keep the compile cache where JAX_COMPILATION_CACHE_DIR
    says and set no other directory; unset, it goes to <repo>/.jax_cache."""
    from repro.utils import compile_cache

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    before = jax.config.jax_compilation_cache_dir
    if env_set:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        assert compile_cache.enable() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == before
        return
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        assert compile_cache.enable() == os.path.join(repo, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == os.path.join(
            repo, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
