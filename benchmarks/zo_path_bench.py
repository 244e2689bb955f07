"""Old-vs-new ZO hot-path benchmark: pytree estimator loop vs the flat-buffer
fused path (DESIGN.md §7), on the softmax-regression model (d = 7850).

Two kinds of rows:

- ``*_us_per_direction`` — measured wall time of one jitted local iterate
  divided by b2 (interpret-mode Pallas on CPU: regression tracking, not a
  TPU projection).
- ``*_hbm_passes*`` / ``*_param_bytes_per_iter`` — the analytic HBM-traffic
  model. One *pass* = one full read+write sweep of the d-sized fp32
  parameter buffer (2·4·d bytes). Counted per direction:

  pytree path (sphere): materialize v (normal-gen write d, norm read d,
  scale read+write 2d → 2.0 passes) + tree_axpy x+μv (read x, read v,
  write → 1.5 passes) = 3.5 passes/direction, and the update replays b2
  more axpy passes (3.5 each). The fused flat path regenerates directions
  in VMEM: each zo_walk step reads and writes x and the update's fp32
  accumulator u (2.0 passes/direction), and the update costs 2.5 passes
  in total: the closing call's pass over u, then x + u (read both, write
  one).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from benchmarks.common import timed
from repro.configs.base import FedZOConfig
from repro.core import fedzo
from repro.data.synthetic import make_classification
from repro.models.simple import softmax_init, softmax_loss
from repro.utils.tree import tree_size


def traffic_model(d: int, b2: int, *, flat: bool, kind: str = "sphere"):
    """Analytic HBM traffic in parameter passes (1 pass = read+write of d
    fp32 words). Returns (passes_per_direction, update_passes_total)."""
    if flat:
        # zo_walk: read + write x and the accumulator u; directions live in
        # VMEM only
        per_direction = 2.0
        # the closing call (read + write u), then x + u
        update_total = 2.5
    else:
        # materialize direction: gen write (0.5) [+ norm read 0.5 + scale
        # read/write 1.0 for sphere] then axpy: read x + read v + write (1.5)
        gen = 2.0 if kind == "sphere" else 0.5
        per_direction = gen + 1.5
        update_total = b2 * (gen + 1.5)
    return per_direction, update_total


def run():
    rows = []
    x, y = make_classification(512, 784, 10, seed=0)
    batch = {"x": jnp.asarray(x[:256]), "y": jnp.asarray(y[:256])}
    params = softmax_init(None)
    d = tree_size(params)
    b2 = 20

    cfg_old = FedZOConfig(b2=b2, lr=1e-3, mu=1e-3)
    cfg_new = dataclasses.replace(cfg_old, flat_params=True)

    step_old = jax.jit(fedzo.make_train_step(softmax_loss, cfg_old))
    step_new = jax.jit(fedzo.make_train_step(softmax_loss, cfg_new))
    rng = jax.random.key(0)

    _, us_old = timed(lambda: step_old(params, batch, rng)[0], n=3)
    _, us_new = timed(lambda: step_new(params, batch, rng)[0], n=3)
    rows.append((f"zo_path/pytree_us_per_direction_d{d}", us_old / b2,
                 us_old))
    rows.append((f"zo_path/flat_us_per_direction_d{d}", us_new / b2,
                 us_new))

    per_old, upd_old = traffic_model(d, b2, flat=False)
    per_new, upd_new = traffic_model(d, b2, flat=True)
    pass_bytes = 2 * 4 * d
    rows.append(("zo_path/pytree_hbm_passes_per_direction", 0.0, per_old))
    rows.append(("zo_path/flat_hbm_passes_per_direction", 0.0, per_new))
    rows.append(("zo_path/pytree_update_hbm_passes_total", 0.0, upd_old))
    rows.append(("zo_path/flat_update_hbm_passes_total", 0.0, upd_new))
    rows.append(("zo_path/pytree_param_bytes_per_iter", 0.0,
                 int((b2 * per_old + upd_old) * pass_bytes)))
    rows.append(("zo_path/flat_param_bytes_per_iter", 0.0,
                 int((b2 * per_new + upd_new) * pass_bytes)))
    rows.append(("zo_path/traffic_reduction_x", 0.0,
                 (b2 * per_old + upd_old) / (b2 * per_new + upd_new)))
    return rows
