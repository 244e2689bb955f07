"""Models for the paper's own experiments (Sec. V).

- ``softmax_regression``: the Fashion-MNIST multinomial classifier of Sec V-B.
- ``fedavg_cnn_*``: FedAvg's MNIST CNN as published (McMahan et al.,
  arXiv:1602.05629, Sec. 3), the standard CNN of the Sec V-B image track:
  d = 1,663,370.
- ``smallcnn_*``: a small LeNet-style CNN (3×3 convs, a linear head) of no
  published source, kept for the tests' small conv track.
- ``cnn_*``: a small conv classifier standing in for the pretrained
  CIFAR-10 network of Carlini & Wagner used in Sec V-A (the container is
  offline; we train this surrogate in-repo on synthetic CIFAR-like data).
- ``cw_attack_loss``: the Carlini-Wagner federated black-box attack loss,
  Eq. (21) — the *optimization variable* is the shared perturbation x, the
  classifier is a frozen black box.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def mean_xent(logits, y):
    """Mean cross-entropy of integer labels — shared by every classifier
    loss here so they stay numerically identical formulations."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
    return jnp.mean(lse - ll)


# ---------------------------------------------------------------------------
# softmax regression (Sec V-B)


def softmax_init(rng, n_features=784, n_classes=10):
    return {"w": jnp.zeros((n_features, n_classes), jnp.float32),
            "b": jnp.zeros((n_classes,), jnp.float32)}


def softmax_logits(params, x):
    return x @ params["w"] + params["b"]


def softmax_loss(params, batch):
    """batch: {"x": [B, F], "y": [B]} -> mean cross-entropy."""
    return mean_xent(softmax_logits(params, batch["x"]), batch["y"])


def softmax_accuracy(params, batch):
    pred = jnp.argmax(softmax_logits(params, batch["x"]), axis=-1)
    return jnp.mean((pred == batch["y"]).astype(jnp.float32))


def _conv_pool(h, w, b=None):
    """'SAME' conv (plus bias), ReLU, 2×2/2 max pool."""
    h = jax.lax.conv_general_dilated(h, w, (1, 1), "SAME",
                                     dimension_numbers=("NHWC", "HWIO",
                                                        "NHWC"))
    if b is not None:
        h = h + b
    h = jax.nn.relu(h)
    return jax.lax.reduce_window(h, -jnp.inf, jax.lax.max, (1, 2, 2, 1),
                                 (1, 2, 2, 1), "VALID")


# ---------------------------------------------------------------------------
# FedAvg's MNIST CNN (McMahan et al., arXiv:1602.05629, Sec. 3)


def fedavg_cnn_init(rng, image_shape=(28, 28, 1), n_classes=10):
    """Two 5×5 'SAME' convs with bias (32 and 64 channels), each followed
    by ReLU and a 2×2/2 max pool, then FC-512 with ReLU and FC to the
    classes: 1,663,370 parameters at 28×28×1 and 10 classes. The paper
    gives no initialisation: weights are He-normal by fan-in, biases 0."""
    h, w, cin = image_shape
    flat = (h // 4) * (w // 4) * 64
    ks = jax.random.split(rng, 4)

    def he(k, shape, fan_in):
        return jax.random.normal(k, shape, jnp.float32) * (2.0 / fan_in) ** 0.5

    return {"conv1_w": he(ks[0], (5, 5, cin, 32), 25 * cin),
            "conv1_b": jnp.zeros((32,), jnp.float32),
            "conv2_w": he(ks[1], (5, 5, 32, 64), 25 * 32),
            "conv2_b": jnp.zeros((64,), jnp.float32),
            "fc1_w": he(ks[2], (flat, 512), flat),
            "fc1_b": jnp.zeros((512,), jnp.float32),
            "fc2_w": he(ks[3], (512, n_classes), 512),
            "fc2_b": jnp.zeros((n_classes,), jnp.float32)}


def fedavg_cnn_logits(params, images):
    """images [B, H, W, C] NHWC, pixels as given -> logits [B, n_classes]."""
    h = _conv_pool(images, params["conv1_w"], params["conv1_b"])
    h = _conv_pool(h, params["conv2_w"], params["conv2_b"])
    h = jax.nn.relu(h.reshape(h.shape[0], -1) @ params["fc1_w"]
                    + params["fc1_b"])
    return h @ params["fc2_w"] + params["fc2_b"]


def fedavg_cnn_loss(params, batch):
    """batch: {"x": [B, H, W, C], "y": [B]} -> mean cross-entropy."""
    return mean_xent(fedavg_cnn_logits(params, batch["x"]), batch["y"])


def fedavg_cnn_accuracy(params, batch):
    pred = jnp.argmax(fedavg_cnn_logits(params, batch["x"]), axis=-1)
    return jnp.mean((pred == batch["y"]).astype(jnp.float32))


# ---------------------------------------------------------------------------
# trainable LeNet-style SmallCNN (tests' small conv track)


def smallcnn_init(rng, image_shape=(28, 28, 1), n_classes=10, width=8):
    """LeNet-style trainable classifier: 3×3 conv → 2×2 pool, twice, then a
    linear head. ``image_shape`` is free (grayscale F-MNIST-like by
    default); the head size follows the two VALID pools (s → ⌊s/2⌋)."""
    h, w, cin = image_shape
    fh, fw = (h // 2) // 2, (w // 2) // 2
    ks = jax.random.split(rng, 3)

    def conv(k, ci, co):
        return (jax.random.normal(k, (3, 3, ci, co), jnp.float32)
                * (2.0 / (9 * ci)) ** 0.5)

    return {"c1": conv(ks[0], cin, width),
            "c2": conv(ks[1], width, 2 * width),
            "w": jax.random.normal(ks[2], (2 * width * fh * fw, n_classes),
                                   jnp.float32) * 0.01,
            "b": jnp.zeros((n_classes,), jnp.float32)}


def smallcnn_logits(params, images):
    """images [B, H, W, C] in [0, 1] -> logits [B, n_classes]."""
    h = images * 2.0 - 1.0
    h = _conv_pool(h, params["c1"])
    h = _conv_pool(h, params["c2"])
    return h.reshape(h.shape[0], -1) @ params["w"] + params["b"]


def smallcnn_loss(params, batch):
    return mean_xent(smallcnn_logits(params, batch["x"]), batch["y"])


def smallcnn_accuracy(params, batch):
    pred = jnp.argmax(smallcnn_logits(params, batch["x"]), axis=-1)
    return jnp.mean((pred == batch["y"]).astype(jnp.float32))


# ---------------------------------------------------------------------------
# small CNN classifier (black-box target for the attack task)


def cnn_init(rng, n_classes=10, width=16):
    ks = jax.random.split(rng, 4)
    def conv(k, cin, cout):
        return (jax.random.normal(k, (3, 3, cin, cout), jnp.float32)
                * (2.0 / (9 * cin)) ** 0.5)
    return {"c1": conv(ks[0], 3, width), "c2": conv(ks[1], width, 2 * width),
            "w": jax.random.normal(ks[2], (2 * width * 8 * 8, n_classes),
                                   jnp.float32) * 0.01,
            "b": jnp.zeros((n_classes,), jnp.float32)}


def cnn_logits(params, images):
    """images [B, 32, 32, 3] in [0, 1] -> logits [B, C]."""
    h = images * 2.0 - 1.0
    h = jax.lax.conv_general_dilated(h, params["c1"], (1, 1), "SAME",
                                     dimension_numbers=("NHWC", "HWIO", "NHWC"))
    h = jax.nn.relu(h)
    h = jax.lax.reduce_window(h, -jnp.inf, jax.lax.max, (1, 2, 2, 1),
                              (1, 2, 2, 1), "VALID")
    h = jax.lax.conv_general_dilated(h, params["c2"], (1, 1), "SAME",
                                     dimension_numbers=("NHWC", "HWIO", "NHWC"))
    h = jax.nn.relu(h)
    h = jax.lax.reduce_window(h, -jnp.inf, jax.lax.max, (1, 2, 2, 1),
                              (1, 2, 2, 1), "VALID")
    h = h.reshape(h.shape[0], -1)
    return h @ params["w"] + params["b"]


def cnn_loss(params, batch):
    return mean_xent(cnn_logits(params, batch["x"]), batch["y"])


# ---------------------------------------------------------------------------
# Carlini-Wagner federated black-box attack loss (Eq. 21)


def _tanh_example(z, x):
    """Adversarial example 0.5*tanh(atanh(2z-1) + x) in [0,1] image space.

    The paper writes images in [-1/2, 1/2]; we keep [0,1] pixels and map
    through the same bijection.
    """
    z_c = jnp.clip(z * 2.0 - 1.0, -1 + 1e-6, 1 - 1e-6)
    return 0.5 * (jnp.tanh(jnp.arctanh(z_c) + x) + 1.0)


def cw_attack_loss(x_pert, batch, classifier_params, c=1.0):
    """Eq. (21): mean over the device's images of
       max(Φ_y(adv) - max_{j≠y} Φ_j(adv), 0) + c‖adv - z‖².

    ``x_pert`` [32*32*3] is the shared perturbation (the FedZO variable);
    the classifier is queried as a black box (no grad taken through it by
    the ZO optimizer).
    """
    z, y = batch["x"], batch["y"]
    adv = _tanh_example(z, x_pert.reshape(1, 32, 32, 3))
    logits = cnn_logits(classifier_params, adv)
    conf_true = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
    masked = logits - 1e9 * jax.nn.one_hot(y, logits.shape[-1])
    conf_best_other = jnp.max(masked, axis=-1)
    margin = jnp.maximum(conf_true - conf_best_other, 0.0)
    dist = jnp.sum(jnp.square(adv - z), axis=(1, 2, 3))
    return jnp.mean(margin + c * dist)


def attack_success(x_pert, batch, classifier_params):
    z, y = batch["x"], batch["y"]
    adv = _tanh_example(z, x_pert.reshape(1, 32, 32, 3))
    pred = jnp.argmax(cnn_logits(classifier_params, adv), axis=-1)
    return jnp.mean((pred != y).astype(jnp.float32))
