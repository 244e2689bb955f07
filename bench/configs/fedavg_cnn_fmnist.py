"""fedavg_cnn_fmnist: FedAvg's MNIST CNN (McMahan et al., arXiv:1602.05629,
Sec. 3), d = 1,663,370, on F-MNIST-shaped 28x28x1 images."""
import math

import jax
import jax.numpy as jnp


def init_params(key, cfg):
    """The starting weights, in the program's pytree layout: He-normal by
    fan-in, biases 0."""
    h, w, cin = cfg["image_shape"]
    flat = (h // 4) * (w // 4) * 64
    shapes = {"conv1_w": (5, 5, cin, 32), "conv2_w": (5, 5, 32, 64),
              "fc1_w": (flat, 512), "fc2_w": (512, cfg["n_classes"])}
    keys = jax.random.split(key, len(shapes))
    out = {}
    for k, (name, shape) in zip(keys, shapes.items()):
        fan_in = math.prod(shape[:-1])
        out[name] = jax.random.normal(k, shape, jnp.float32) \
            * (2.0 / fan_in) ** 0.5
        out[name[:-1] + "b"] = jnp.zeros((shape[-1],), jnp.float32)
    return out


def program_model(cfg):
    """The system under test's (loss, accuracy) for this model."""
    from repro.models import simple
    return simple.fedavg_cnn_loss, simple.fedavg_cnn_accuracy


def _pool(h):
    return jax.lax.reduce_window(h, -jnp.inf, jax.lax.max, (1, 2, 2, 1),
                                 (1, 2, 2, 1), "VALID")


def ref_logits(params, x, ops):
    """Plain reference forward: two 'SAME' convs with bias, each with ReLU
    and a 2x2/2 max pool, then FC-512 with ReLU and FC to the classes."""
    h = _pool(jax.nn.relu(ops.conv(x, params["conv1_w"]) + params["conv1_b"]))
    h = _pool(jax.nn.relu(ops.conv(h, params["conv2_w"]) + params["conv2_b"]))
    h = jax.nn.relu(ops.matmul(h.reshape(h.shape[0], -1), params["fc1_w"])
                    + params["fc1_b"])
    return ops.matmul(h, params["fc2_w"]) + params["fc2_b"]


def flops_per_sample(cfg):
    """Multiply-adds of one forward sample, times two: conv1 (25 taps of
    the input channels into 32, at every pixel), conv2 (25 x 32 into 64 on
    the pooled map), FC-512 and FC-10. At 28x28x1: 24,546,304."""
    h, w, cin = cfg["image_shape"]
    conv1 = h * w * 32 * 25 * cin
    conv2 = (h // 2) * (w // 2) * 64 * 25 * 32
    flat = (h // 4) * (w // 4) * 64
    return 2 * (conv1 + conv2 + flat * 512 + 512 * cfg["n_classes"])
