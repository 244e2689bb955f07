"""softmax_fmnist: multinomial logistic regression 784 -> 10 (the paper's
Sec. V-B model), d = 7,850."""
import jax
import jax.numpy as jnp


def init_params(key, cfg):
    """The starting weights, in the program's pytree layout: LeCun-normal
    w ~ N(0, 1/n_features), b = 0."""
    return {"w": jax.random.normal(key, (cfg["n_features"], cfg["n_classes"]),
                                   jnp.float32) * cfg["n_features"] ** -0.5,
            "b": jnp.zeros((cfg["n_classes"],), jnp.float32)}


def program_model(cfg):
    """The system under test's (loss, accuracy) for this model."""
    from repro.models import simple
    return simple.softmax_loss, simple.softmax_accuracy


def ref_logits(params, x, ops):
    """Plain reference forward: x @ w + b."""
    return ops.matmul(x, params["w"]) + params["b"]


def flops_per_sample(cfg):
    """Multiply-adds of one forward sample, times two."""
    return 2 * cfg["n_features"] * cfg["n_classes"]
