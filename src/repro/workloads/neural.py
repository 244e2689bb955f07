"""Engine-native neural FedZO: the paper's Sec. V-B training track
(DESIGN.md §11).

The headline experiments (Figs. 2–6) train *neural* models — softmax
regression and a LeNet-style CNN on (Fashion-)MNIST/FEMNIST — under varying
local iterates H, participating devices M, and AirComp SNR. This module is
the ``models ↔ sim`` bridge that makes any init/loss/accuracy triple a
first-class FedZO workload: the model trains MeZO-style (forward passes
only — ``jax.grad`` of the model is never taken), its parameter pytree
flows through ``FlatParams`` on the flat/wide hot paths unchanged, and the
whole multi-round run — participation draws, minibatch sampling, the H·b2
perturbed forwards per client, aggregation (plain / size-weighted / AirComp
/ channel-truncated / clients-mesh sharded), and the in-scan top-1 accuracy
eval — executes as ONE compiled program via ``sim.run_experiment``.

Four registered tracks (``make_task(name)``):

- ``softmax``     — the Sec. V-B multinomial classifier (models/simple).
- ``fedavg_cnn``  — FedAvg's MNIST CNN as published (models/simple,
  d = 1,663,370), with its own lr and μ as the track's defaults
  (``default_config``).
- ``cnn``         — the trainable LeNet-style SmallCNN (models/simple).
- ``transformer`` — a tiny patch-token transformer head built from the
  LM stack's blocks (models/transformer.init_classifier).

Clients hold Dirichlet(α)-label-skewed shards of a synthetic
class-conditional Gaussian problem (``data.synthetic``; the container is
offline, so F-MNIST is replaced by a generator that preserves the problem's
shape), stacked once into a device-resident ``ClientStore``.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro import sim
from repro.configs.base import FedZOConfig, ModelConfig
from repro.data.synthetic import federated_classification
from repro.models import simple, transformer


class NeuralTask(NamedTuple):
    """A trainable federated classification problem: the init/loss/accuracy
    triple under the engine's ``loss(params, batch) -> scalar`` contract,
    the client shards (host lists + stacked device store), and the pooled
    held-out test batch the in-scan eval reads."""
    name: str
    init: Callable        # (seed) -> params pytree
    loss: Callable        # (params, batch) -> scalar mean cross-entropy
    accuracy: Callable    # (params, batch) -> top-1 accuracy
    clients: list
    store: sim.ClientStore
    test: dict            # pooled {"x", "y"} held-out batch


def _softmax_triple(n_features, n_classes, kw):
    return (lambda seed: simple.softmax_init(None, n_features, n_classes),
            simple.softmax_loss, simple.softmax_accuracy, None)


def _cnn_triple(n_features, n_classes, kw):
    shape = kw.pop("image_shape")
    width = kw.pop("width", 8)
    return (lambda seed: simple.smallcnn_init(jax.random.key(seed), shape,
                                              n_classes, width),
            simple.smallcnn_loss, simple.smallcnn_accuracy, shape)


def _fedavg_cnn_triple(n_features, n_classes, kw):
    shape = kw.pop("image_shape")
    return (lambda seed: simple.fedavg_cnn_init(jax.random.key(seed), shape,
                                                n_classes),
            simple.fedavg_cnn_loss, simple.fedavg_cnn_accuracy, shape)


def _transformer_triple(n_features, n_classes, kw):
    n_patches = kw.pop("n_patches", 8)
    if n_features % n_patches:
        raise ValueError(f"n_features={n_features} must split into "
                         f"{n_patches} patch tokens")
    d_model = kw.pop("d_model", 32)
    n_heads = kw.pop("n_heads", 2)
    cfg = ModelConfig(
        name="tiny-patch-cls", family="dense",
        source="repro-internal tiny head (DESIGN.md §11)",
        n_layers=kw.pop("n_layers", 1), d_model=d_model,
        d_ff=kw.pop("d_ff", 64), vocab=0, n_heads=n_heads,
        n_kv_heads=n_heads, head_dim=d_model // n_heads,
        act="gelu", dtype="float32")
    patch_dim = n_features // n_patches
    return (lambda seed: transformer.init_classifier(
                jax.random.key(seed), cfg, n_patches=n_patches,
                patch_dim=patch_dim, n_classes=n_classes),
            lambda p, b: transformer.classifier_loss(p, b, cfg),
            lambda p, b: transformer.classifier_accuracy(p, b, cfg),
            None)


_TRIPLES = {"softmax": _softmax_triple, "cnn": _cnn_triple,
            "fedavg_cnn": _fedavg_cnn_triple,
            "transformer": _transformer_triple}
_IMAGE_TRACKS = ("cnn", "fedavg_cnn")

# A track's own defaults over ``default_config``'s shared ones. FedAvg's
# CNN diverges at the shared lr (5e-3) and drifts at 1e-3; lr 3e-4 and
# μ 0.015 come from a sweep on the chip.
_TRACK_CONFIG = {"fedavg_cnn": dict(lr=3e-4, mu=0.015)}


def make_task(name="softmax", **kw) -> NeuralTask:
    """Build a registered neural FedZO task.

    ``name``: softmax | fedavg_cnn | cnn | transformer. The data is a
    synthetic class-conditional Gaussian problem (image-shaped and squashed
    to [0, 1] pixels for the image tracks) split ``partition``-wise across
    ``n_clients`` (Dirichlet label skew by default; see ``_make_task`` for
    the data defaults). Extra keywords reach the model constructor (cnn:
    image_shape, width; fedavg_cnn: image_shape; transformer: n_patches,
    n_layers, d_model, d_ff, n_heads).
    Cached: repeated calls with identical arguments (tests, benchmarks,
    figures) reuse the built store.
    """
    if kw.get("image_shape") is not None:
        # normalize before the cache layer — a list would fail lru_cache's
        # key hashing before the body could coerce it
        kw["image_shape"] = tuple(kw["image_shape"])
    return _make_task(name, **kw)


@functools.lru_cache(maxsize=8)
def _make_task(name, *, n_train=2000, n_test=512, n_clients=10,
               n_features=784, n_classes=10, seed=0, scale=1.0,
               partition="dirichlet", alpha=0.5, **model_kw) -> NeuralTask:
    if name not in _TRIPLES:
        raise ValueError(f"unknown neural task {name!r}; registered: "
                         f"{sorted(_TRIPLES)}")
    kw = dict(model_kw)
    if name in _IMAGE_TRACKS:
        shape = tuple(kw.get("image_shape") or (28, 28, 1))
        kw["image_shape"] = shape
        n_features = 1
        for s in shape:
            n_features *= s
    init, loss, acc, image_shape = _TRIPLES[name](n_features, n_classes, kw)
    if kw:
        # the triples pop what they consume — a misspelled model kwarg must
        # fail here, not silently build-and-cache a default-model task
        raise ValueError(f"unknown model kwargs for task {name!r}: "
                         f"{sorted(kw)}")
    clients, test = federated_classification(
        n_train, n_test, n_clients, n_features=n_features,
        n_classes=n_classes, seed=seed, scale=scale,
        image_shape=image_shape, partition=partition, alpha=alpha)
    return NeuralTask(name=name, init=init, loss=loss, accuracy=acc,
                      clients=clients, store=sim.build_store(clients),
                      test={"x": jnp.asarray(test["x"]),
                            "y": jnp.asarray(test["y"])})


def params_init(task: NeuralTask, seed: int = 0):
    """Fresh model parameters for a task (the FedZO server state x^0)."""
    return task.init(seed)


def task_eval(task: NeuralTask, max_rows: int = 1024):
    """jit-traceable in-scan eval: pooled top-1 test accuracy + test loss.
    ``max_rows`` bounds the per-eval forward (the eval runs INSIDE the
    compiled scan every k rounds, so its cost is paid rounds/k times)."""
    test = jax.tree.map(lambda a: a[:max_rows], task.test)

    def ev(params):
        return {"test_acc": task.accuracy(params, test),
                "test_loss": task.loss(params, test)}

    return ev


def default_config(task: NeuralTask, **overrides) -> FedZOConfig:
    """Sec. V-B-shaped hyperparameters at container scale: partial
    participation, H=5 local iterates, b2=20 directions, size-weighted
    aggregation for the skewed shards; a track's own defaults
    (``_TRACK_CONFIG``) over these, and ``overrides`` over both."""
    kw = dict(n_devices=task.store.n_clients,
              n_participating=max(2, task.store.n_clients // 2),
              local_iters=5, lr=5e-3, mu=1e-3, b1=25, b2=20,
              weight_by_size=True)
    kw.update(_TRACK_CONFIG.get(task.name, {}))
    kw.update(overrides)
    return FedZOConfig(**kw)


def run(task: NeuralTask, cfg: FedZOConfig, rounds: int, *, eval_every=2,
        mesh=None, eval_rows=1024, **kw) -> sim.ExperimentResult:
    """Train the task's model with FedZO inside ONE compiled program.

    ``mesh`` (a ``sim.make_clients_mesh()``) fans the M sampled clients out
    over a device mesh via the sharded round — the experiment is still one
    scan. All aggregation paths (flat / wide / AirComp / channel-schedule /
    weighted) come straight from ``cfg``.
    """
    if mesh is not None:
        kw.setdefault("round_fn", sim.make_sharded_round(task.loss, cfg,
                                                         mesh))
    return sim.run_experiment(task.loss, params_init(task, cfg.seed),
                              task.store, cfg, rounds,
                              eval_fn=task_eval(task, eval_rows),
                              eval_every=eval_every, **kw)


def run_sweep(task: NeuralTask, base_cfg: FedZOConfig, scenarios, rounds, *,
              eval_every=2, eval_rows=1024, out_csv=None) -> list:
    """A scenario grid over the task — {H, M} group per compile, the
    {snr_db, lr, mu, h_min, seed} axes vmapped (sim/sweep.py); per-round
    metrics and the in-scan accuracy curve land as long-format CSV."""
    return sim.run_sweep(task.loss, params_init(task, base_cfg.seed),
                         task.store, base_cfg, scenarios, rounds,
                         eval_fn=task_eval(task, eval_rows),
                         eval_every=eval_every, out_csv=out_csv)
