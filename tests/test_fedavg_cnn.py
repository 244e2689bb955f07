"""FedAvg's MNIST CNN (McMahan et al., arXiv:1602.05629, Sec. 3) as the
``fedavg_cnn`` track: the published widths and parameter count, the
forward against a plain float32 reference written here, the flat-kernel
experiment against the pytree counter path, and the ``fedzo.forward``
scope on its convolutions.
"""
from __future__ import annotations

import math
import re

import jax
import jax.numpy as jnp
import numpy as np

from repro import sim
from repro.models import simple
from repro.workloads import neural

PUBLISHED = {"conv1_w": (5, 5, 1, 32), "conv1_b": (32,),
             "conv2_w": (5, 5, 32, 64), "conv2_b": (64,),
             "fc1_w": (3136, 512), "fc1_b": (512,),
             "fc2_w": (512, 10), "fc2_b": (10,)}


def _random_params(seed, image_shape=(28, 28, 1)):
    """Seeded random weights at the published widths, biases included (the
    init's zero biases would leave the bias adds untested)."""
    p = simple.fedavg_cnn_init(jax.random.key(seed), image_shape)
    keys = jax.random.split(jax.random.key(seed + 1), len(p))
    return {k: (v if k.endswith("_w") else
                0.1 * jax.random.normal(kk, v.shape, jnp.float32))
            for kk, (k, v) in zip(keys, sorted(p.items()))}


def _plain_conv_same(x, w, b):
    """5×5 'SAME' convolution as 25 shifted matmuls over a zero-padded
    input: NHWC x, HWIO w."""
    kh, kw = w.shape[:2]
    xp = jnp.pad(x, ((0, 0), (kh // 2, kh // 2), (kw // 2, kw // 2), (0, 0)))
    h, wd = x.shape[1:3]
    out = sum(jnp.einsum("bhwc,co->bhwo", xp[:, i:i + h, j:j + wd], w[i, j])
              for i in range(kh) for j in range(kw))
    return out + b


def _plain_pool(x):
    b, h, w, c = x.shape
    return x.reshape(b, h // 2, 2, w // 2, 2, c).max(axis=(2, 4))


def _plain_logits(p, x):
    h = _plain_pool(jnp.maximum(_plain_conv_same(x, p["conv1_w"],
                                                 p["conv1_b"]), 0.0))
    h = _plain_pool(jnp.maximum(_plain_conv_same(h, p["conv2_w"],
                                                 p["conv2_b"]), 0.0))
    h = jnp.maximum(h.reshape(h.shape[0], -1) @ p["fc1_w"] + p["fc1_b"], 0.0)
    return h @ p["fc2_w"] + p["fc2_b"]


def test_published_widths_and_parameter_count():
    p = simple.fedavg_cnn_init(jax.random.key(0))
    assert {k: v.shape for k, v in p.items()} == PUBLISHED
    assert sum(math.prod(v.shape) for v in p.values()) == 1_663_370
    assert all(v.dtype == jnp.float32 for v in p.values())
    assert all(not np.any(np.asarray(p[k])) for k in p if k.endswith("_b"))


def test_logits_match_plain_float32_forward():
    """Tolerance: the two forwards sum the same float32 products in another
    order (conv2 sums 800 per output, FC-512 3,136), so they differ by a
    few ulps of the partial sums; 2e-5 absolute on logits of order one is
    ~100 ulps, and a forward at one bfloat16 pass misses it by far."""
    p = _random_params(3)
    x = jax.random.uniform(jax.random.key(4), (4, 28, 28, 1), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(_plain_logits)(p, x)
        got = jax.jit(simple.fedavg_cnn_logits)(p, x)
    assert got.shape == (4, 10)
    assert float(jnp.max(jnp.abs(want))) > 0.5
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=2e-5)


def _task():
    """The fedavg_cnn track at 8×8×1 images, every width as published
    (d = 188,810: three 496-row kernel blocks)."""
    return neural.make_task("fedavg_cnn", n_train=160, n_test=32,
                            n_clients=4, image_shape=(8, 8, 1), seed=1)


def _cnn_run(**kw):
    task = _task()
    cfg = neural.default_config(task, n_participating=2, local_iters=2,
                                b1=4, b2=3, seed=5, direction_conv="counter",
                                **kw)
    return sim.run_experiment(task.loss, task.init(0), task.store, cfg, 2)


def test_track_defaults_are_its_lr_and_mu():
    cfg = neural.default_config(_task())
    assert (cfg.lr, cfg.mu) == (3e-4, 0.015)
    assert cfg.flat_block_rows == 0


def test_flat_experiment_matches_pytree_counter_path():
    """Two rounds of the flat-kernel plan walk the pytree counter path's
    trajectory: the mean local losses within the flat-against-pytree
    tolerance of test_zo_flat.py, and each leaf's change from the start
    within 1e-2 of its norm. The two paths draw the same counter
    directions and differ only in the order of float32 sums, which the
    coefficient d·ΔL/μ turns into about 1e-3 of the change (5e-4 to 9e-4
    on every leaf here); an update of the wrong sign reads 2 and one
    scaled by 10 % reads 0.1. The changes themselves (up to 2e-2 a
    coordinate at the track's lr) are too small for an absolute bound on
    the parameters to tell these apart."""
    flat = _cnn_run(flat_params=True)
    tree = _cnn_run(flat_params=False)
    np.testing.assert_allclose(np.asarray(flat.metrics["mean_local_loss"]),
                               np.asarray(tree.metrics["mean_local_loss"]),
                               rtol=2e-3, atol=2e-4)
    p0 = _task().init(0)
    for k in sorted(p0):
        got = np.asarray(flat.params[k]) - np.asarray(p0[k])
        want = np.asarray(tree.params[k]) - np.asarray(p0[k])
        assert np.linalg.norm(want) > 0, k
        rel = np.linalg.norm(got - want) / np.linalg.norm(want)
        assert rel < 1e-2, (k, rel)


def test_forward_scope_on_convolutions_inside_query():
    task = _task()
    cfg = neural.default_config(task, n_participating=2, local_iters=1,
                                b1=4, b2=2, flat_params=True,
                                direction_conv="counter")
    fn = sim.make_experiment_fn(task.loss, cfg, 1)
    text = fn.lower(task.init(0), None, jax.random.key(0), None, None, None,
                    task.store).compile().as_text()
    convs = re.findall(r'= [^\n]* convolution\([^\n]*op_name="([^"]*)"',
                       text)
    assert convs
    for path in convs:
        assert "fedzo.query/fedzo.forward/" in path, path
