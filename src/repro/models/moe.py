"""Mixture-of-Experts with sort-based capacity dispatch and expert parallelism.

Design (see DESIGN.md §5):
- Tokens are sharded over the ``data`` mesh axis, experts over ``model``.
- Dispatch is *local masked*: every device routes its own tokens, keeps only
  assignments that land on its locally-owned experts, scatters them into a
  fixed-capacity [E_local, C, d] buffer (deterministic shapes under jit),
  runs the expert FFNs as batched matmuls, scatters back, and psums partial
  outputs over ``model``. No all_to_all is needed because activations are
  replicated along ``model`` (standard tensor-parallel residual stream).
- Expert weights are additionally FSDP-sharded over ``data`` on the FFN dim
  and all-gathered just-in-time (per layer, inside the scan) — this is what
  makes 671B fit 16 GB/chip.
- The token gather/scatter runs in ``top_k`` chunks of T tokens each so the
  transient dispatch values stay at [T, d] instead of [T·k, d] (7.5 GB/device
  for DeepSeek-V3 at train_4k — the chunking is load-bearing).

The same ``_route_and_compute`` body runs unsharded for CPU smoke tests
(mesh=None), so the distributed path is covered by the single-device oracle.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.layers import _act, dense_init, init_mlp, mlp_fwd


def init_moe(rng, cfg, dtype):
    d, E, f = cfg.d_model, cfg.n_experts, cfg.moe_d_ff or cfg.d_ff
    ks = jax.random.split(rng, 5)
    def ew(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32) / fan_in ** 0.5).astype(dtype)
    p = {
        "router": dense_init(ks[0], d, E, jnp.float32),  # router kept fp32
        "w_gate": ew(ks[1], (E, d, f), d),
        "w_up": ew(ks[2], (E, d, f), d),
        "w_down": ew(ks[3], (E, f, d), f),
    }
    if cfg.n_shared_experts:
        p["shared"] = init_mlp(ks[4], d, (cfg.moe_d_ff or cfg.d_ff) * cfg.n_shared_experts,
                               cfg.act, dtype)
    return p


def _capacity(n_tokens, cfg, e_local):
    per_expert = n_tokens * cfg.top_k / cfg.n_experts
    c = int(per_expert * cfg.capacity_factor) + 1
    return max(c, cfg.top_k)  # floor so tiny smoke shapes don't drop everything


def _route_and_compute(x_flat, p_router, w_gate, w_up, w_down, *,
                       cfg, e_offset, e_local, capacity):
    """Dispatch tokens in x_flat [T, d] to local experts [e_offset, e_offset+e_local).

    Returns (partial_out [T, d], (me, ce) partial load-balance stats).
    """
    T, d = x_flat.shape
    k = cfg.top_k
    # router matmul in activation dtype (upcasting x_flat materializes a
    # fp32 copy of the full token stream — 1.75 GB/layer at train_4k);
    # softmax accumulates in fp32 on the small [T, E] logits.
    logits = (x_flat @ p_router.astype(x_flat.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, k)                    # [T, k]
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)  # renormalize top-k

    fe = idx.reshape(-1)                                    # [T*k] expert ids
    ft = jnp.tile(jnp.arange(T), (k, 1)).T.reshape(-1)      # token of each slot
    fg = gates.reshape(-1)
    is_local = (fe >= e_offset) & (fe < e_offset + e_local)
    le = jnp.where(is_local, fe - e_offset, e_local)        # e_local = dustbin
    order = jnp.argsort(le, stable=True)
    se, st, sg = le[order], ft[order], fg[order]
    counts = jnp.bincount(se, length=e_local + 1)
    starts = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                              jnp.cumsum(counts)[:-1]])
    pos = jnp.arange(T * k) - starts[se]
    keep = (se < e_local) & (pos < capacity)
    se_c = jnp.where(keep, se, e_local)
    pos_c = jnp.where(keep, pos, 0)

    # chunked scatter: k rounds of [T]-sized gather+scatter keep transients at
    # [T, d] (instead of one [T*k, d] gather).
    se_k, st_k = se_c.reshape(k, T), st.reshape(k, T)
    pos_k, keep_k, sg_k = pos_c.reshape(k, T), keep.reshape(k, T), sg.reshape(k, T)
    buf = jnp.zeros((e_local + 1, capacity, d), x_flat.dtype)
    for j in range(k):
        vals = jnp.where(keep_k[j][:, None], x_flat[st_k[j]], 0)
        buf = buf.at[se_k[j], pos_k[j]].add(vals)
    h_in = buf[:e_local]                                     # [E_l, C, d]

    if cfg.act in ("swiglu", "geglu"):
        h = _act(jnp.einsum("ecd,edf->ecf", h_in, w_gate), cfg.act) \
            * jnp.einsum("ecd,edf->ecf", h_in, w_up)
    else:
        h = _act(jnp.einsum("ecd,edf->ecf", h_in, w_up), cfg.act)
    out_buf = jnp.einsum("ecf,efd->ecd", h, w_down)          # [E_l, C, d]
    out_buf = jnp.concatenate(
        [out_buf, jnp.zeros((1, capacity, d), out_buf.dtype)], axis=0)

    out = jnp.zeros((T, d), x_flat.dtype)
    for j in range(k):
        w = jnp.where(keep_k[j], sg_k[j], 0).astype(x_flat.dtype)
        out = out.at[st_k[j]].add(out_buf[se_k[j], pos_k[j]] * w[:, None])

    # Switch-style load-balance stats (partial; caller normalizes):
    me = jnp.sum(probs, axis=0)                              # [E]
    ce = jnp.bincount(fe, length=cfg.n_experts).astype(jnp.float32)
    return out, (me, ce)


def moe_fwd(p, cfg, x, mesh=None, data_axes=None, model_axis="model"):
    """x [B, S, d] -> (out [B, S, d], aux_loss scalar)."""
    B, S, d = x.shape
    x_flat = x.reshape(B * S, d)
    E = cfg.n_experts
    if mesh is not None and data_axes is None:
        # batch axes of this mesh ('pod' is a batch axis for the forward)
        data_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)

    if mesh is None:
        cap = _capacity(B * S, cfg, E)
        out, (me, ce) = _route_and_compute(
            x_flat, p["router"], p["w_gate"], p["w_up"], p["w_down"],
            cfg=cfg, e_offset=0, e_local=E, capacity=cap)
    else:
        n_data = 1
        for a in data_axes:
            n_data *= mesh.shape[a]
        n_model = mesh.shape[model_axis]
        shard_tokens = (B * S) % n_data == 0 and n_data > 1
        fsdp_axis = data_axes[-1]

        if shard_tokens:
            # train/prefill layout: tokens over data, experts over model,
            # expert FFN dim FSDP over data (gathered just-in-time).
            e_local = max(E // n_model, 1)
            cap = _capacity((B * S) // n_data, cfg, e_local)

            def body(xl, rw, wg, wu, wd):
                wg = jax.lax.all_gather(wg, fsdp_axis, axis=2, tiled=True)
                wu = jax.lax.all_gather(wu, fsdp_axis, axis=2, tiled=True)
                wd = jax.lax.all_gather(wd, fsdp_axis, axis=1, tiled=True)
                e_off = jax.lax.axis_index(model_axis) * e_local
                out, (me, ce) = _route_and_compute(
                    xl, rw, wg, wu, wd, cfg=cfg, e_offset=e_off,
                    e_local=e_local, capacity=cap)
                out = jax.lax.psum(out, model_axis)
                me = jax.lax.psum(me, data_axes)
                ce = jax.lax.psum(ce, data_axes)
                return out, me, ce

            dspec = P(data_axes if len(data_axes) > 1 else data_axes[0], None)
            in_specs = (dspec, P(None, None),
                        P(model_axis, None, fsdp_axis),
                        P(model_axis, None, fsdp_axis),
                        P(model_axis, fsdp_axis, None))
        else:
            # decode layout (tiny token count): tokens replicated across the
            # mesh, experts sharded over ``model`` (weights resharded by the
            # in_specs from their FSDP at-rest layout). out needs the psum
            # over model; the router stats are computed identically on every
            # device (replicated tokens + replicated router) so they need no
            # collective at all.
            e_local = max(E // n_model, 1)
            cap = _capacity(B * S, cfg, e_local)

            def body(xl, rw, wg, wu, wd):
                e_off = jax.lax.axis_index(model_axis) * e_local
                out, (me, ce) = _route_and_compute(
                    xl, rw, wg, wu, wd, cfg=cfg, e_offset=e_off,
                    e_local=e_local, capacity=cap)
                out = jax.lax.psum(out, model_axis)
                return out, me, ce

            dspec = P(None, None)
            in_specs = (dspec, P(None, None),
                        P(model_axis, None, None), P(model_axis, None, None),
                        P(model_axis, None, None))

        out, me, ce = jax.shard_map(
            body,
            mesh=mesh,
            in_specs=in_specs,
            out_specs=(dspec, P(None), P(None)),
            axis_names={*data_axes, model_axis},
        )(x_flat, p["router"], p["w_gate"], p["w_up"], p["w_down"])

    n_tok = B * S
    me = me / n_tok
    ce = ce / (n_tok * cfg.top_k)
    aux = cfg.router_aux_coef * E * jnp.sum(me * ce)

    out = out.reshape(B, S, d)
    if cfg.n_shared_experts:
        out = out + mlp_fwd(p["shared"], x, cfg.act)
    return out, aux
