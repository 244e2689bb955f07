"""Durable checkpointing (DESIGN.md §12).

Two layers share one on-disk format (flattened-pytree-path ``.npz`` + JSON
sidecar, exact restore tested):

- ``save``/``restore`` — the original single-snapshot params API.
- ``save_run_state``/``latest_run_state``/``restore_run_state`` — durable
  engine snapshots of the FULL ``run_experiment`` carry (params, momentum,
  key data, fault state, metrics ring, eval buffer) at a round index,
  written ATOMICALLY: the snapshot lands in a temp dir that is renamed
  into place, and only then is the ``LATEST`` pointer file swapped (itself
  via tmp + ``os.replace``). A SIGKILL at any instant leaves either the
  previous consistent snapshot or the new one — never a torn write; stale
  ``*.tmp*`` debris is ignored and swept on the next save.

Every sidecar records the repro config hash, the jax version, and the
wall-clock write time; restore warns when the running jax version differs
(bit-exact trajectories are only pinned per jax version — the golden CI
pin exists for the same reason).
"""
from __future__ import annotations

import dataclasses
import datetime
import hashlib
import json
import os
import shutil
import warnings

import jax
import numpy as np


def _flatten(tree):
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(kp): np.asarray(v) for kp, v in flat}, treedef


def config_hash(cfg) -> str:
    """Stable short hash of a config (dataclass or dict) — recorded in every
    sidecar so a restore into a different experiment is detectable."""
    if dataclasses.is_dataclass(cfg):
        cfg = dataclasses.asdict(cfg)
    blob = json.dumps(cfg, sort_keys=True, default=str).encode()
    return hashlib.sha256(blob).hexdigest()[:16]


def _sidecar(meta=None, *, step=None) -> dict:
    md = {"jax_version": jax.__version__,
          "created_at": datetime.datetime.now(
              datetime.timezone.utc).isoformat()}
    if step is not None:
        md["step"] = int(step)
    if meta is not None:
        if dataclasses.is_dataclass(meta):
            md["config_hash"] = config_hash(meta)
            meta = dataclasses.asdict(meta)
        md["meta"] = meta
    return md


def _check_jax_version(md: dict, path: str):
    want = md.get("jax_version")
    if want is not None and want != jax.__version__:
        warnings.warn(
            f"checkpoint {path} was written under jax {want} but this is "
            f"jax {jax.__version__} — bit-exact trajectories are only "
            f"pinned per jax version (see the golden-fixture CI pin)")


def _restore_arrays(npz_path, like):
    """Load flattened arrays into the structure of ``like`` with loud,
    actionable errors: a missing key or a shape mismatch names the exact
    pytree path and both shapes instead of dying on a bare KeyError /
    AssertionError. Only the template's shapes and dtypes are read, so its
    buffers may already have been donated to an earlier run."""
    loaded = np.load(npz_path)
    flat, treedef = jax.tree_util.tree_flatten_with_path(like)
    leaves = []
    for kp, ref in flat:
        name = jax.tree_util.keystr(kp)
        if name not in loaded.files:
            raise ValueError(
                f"checkpoint {npz_path} has no entry for pytree leaf "
                f"{name!r} (file holds {sorted(loaded.files)}); was it "
                f"written from a different model/carry structure?")
        arr = loaded[name]
        if arr.shape != np.shape(ref):
            raise ValueError(
                f"checkpoint {npz_path} leaf {name!r} has shape "
                f"{arr.shape} but the restore target expects "
                f"{np.shape(ref)}; restoring into a different "
                f"model/config?")
        dtype = ref.dtype if hasattr(ref, "dtype") else np.asarray(ref).dtype
        leaves.append(jax.numpy.asarray(arr, dtype=dtype))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def save(path, params, *, step=0, meta=None):
    os.makedirs(path, exist_ok=True)
    arrays, _ = _flatten(params)
    np.savez(os.path.join(path, "params.npz"), **arrays)
    with open(os.path.join(path, "meta.json"), "w") as f:
        json.dump(_sidecar(meta, step=step), f, indent=1)


def restore(path, params_like):
    """Restore into the structure of ``params_like`` (shape/dtype
    preserved). Returns ``(params, step)``."""
    with open(os.path.join(path, "meta.json")) as f:
        md = json.load(f)
    _check_jax_version(md, path)
    params = _restore_arrays(os.path.join(path, "params.npz"), params_like)
    return params, md["step"]


# -- durable run-state snapshots (the engine's full carry) -------------------

_LATEST = "LATEST"


def _snapshot_name(round_idx: int) -> str:
    return f"round_{round_idx:08d}"


def save_run_state(ckpt_dir, state, *, round_idx: int, meta=None,
                   keep: int = 3) -> str:
    """Atomically snapshot a full carry pytree at ``round_idx``.

    Write protocol: stage into ``<name>.tmp.<pid>``, ``os.rename`` the dir
    into place (atomic on POSIX), then swap the ``LATEST`` pointer file via
    tmp + ``os.replace``. Old snapshots beyond the newest ``keep`` (and any
    stale tmp debris from killed writers) are swept AFTER the pointer
    swap, so the pointer never dangles. Returns the snapshot path.
    """
    os.makedirs(ckpt_dir, exist_ok=True)
    name = _snapshot_name(round_idx)
    final = os.path.join(ckpt_dir, name)
    tmp = os.path.join(ckpt_dir, f"{name}.tmp.{os.getpid()}")
    if os.path.exists(tmp):
        shutil.rmtree(tmp)
    os.makedirs(tmp)
    arrays, _ = _flatten(state)
    np.savez(os.path.join(tmp, "state.npz"), **arrays)
    md = _sidecar(dict(meta or {}, round=int(round_idx)))
    with open(os.path.join(tmp, "meta.json"), "w") as f:
        json.dump(md, f, indent=1)
    if os.path.exists(final):  # re-save of the same round (e.g. rollback
        shutil.rmtree(final)   # loops): replace wholesale
    os.rename(tmp, final)
    ptr_tmp = os.path.join(ckpt_dir, f"{_LATEST}.tmp.{os.getpid()}")
    with open(ptr_tmp, "w") as f:
        f.write(name)
    os.replace(ptr_tmp, os.path.join(ckpt_dir, _LATEST))
    _sweep(ckpt_dir, keep=keep)
    return final


def _snapshots(ckpt_dir):
    try:
        entries = os.listdir(ckpt_dir)
    except FileNotFoundError:
        return []
    return sorted(e for e in entries
                  if e.startswith("round_") and ".tmp" not in e
                  and os.path.isdir(os.path.join(ckpt_dir, e)))


def _sweep(ckpt_dir, *, keep: int):
    latest = None
    ptr = os.path.join(ckpt_dir, _LATEST)
    if os.path.exists(ptr):
        with open(ptr) as f:
            latest = f.read().strip()
    snaps = _snapshots(ckpt_dir)
    drop = set(snaps[:-keep]) if keep > 0 else set()
    drop.discard(latest)
    for e in os.listdir(ckpt_dir):
        if e in drop or (".tmp" in e and e != latest):
            target = os.path.join(ckpt_dir, e)
            if os.path.isdir(target):
                shutil.rmtree(target, ignore_errors=True)
            elif e != _LATEST:
                try:
                    os.remove(target)
                except OSError:
                    pass


def latest_run_state(ckpt_dir):
    """Path of the newest consistent snapshot in ``ckpt_dir`` (via the
    ``LATEST`` pointer, falling back to the highest complete round dir),
    or None when the dir holds no snapshot — a fresh start."""
    ptr = os.path.join(ckpt_dir, _LATEST)
    if os.path.exists(ptr):
        with open(ptr) as f:
            name = f.read().strip()
        cand = os.path.join(ckpt_dir, name)
        if os.path.exists(os.path.join(cand, "meta.json")):
            return cand
    for name in reversed(_snapshots(ckpt_dir)):
        cand = os.path.join(ckpt_dir, name)
        if os.path.exists(os.path.join(cand, "meta.json")):
            return cand
    return None


def read_sidecar(snapshot_path) -> dict:
    """The raw JSON sidecar of a snapshot (save/save_run_state layouts
    both) WITHOUT loading any arrays — provenance readers (the obs run
    manifest cross-check, tooling that lists checkpoint dirs) use this to
    get at ``config_hash``/``jax_version``/``round`` cheaply. A
    ``config_hash`` recorded inside a dict-meta (the run-state layout) is
    hoisted to the top level so both layouts read uniformly."""
    with open(os.path.join(snapshot_path, "meta.json")) as f:
        md = json.load(f)
    meta = md.get("meta")
    if isinstance(meta, dict) and "config_hash" not in md \
            and "config_hash" in meta:
        md["config_hash"] = meta["config_hash"]
    return md


def restore_run_state(snapshot_path, state_like):
    """Restore a full-carry snapshot into the structure of ``state_like``.
    Returns ``(state, meta dict)`` where meta is the flattened sidecar
    (round, lr, events, config_hash, ...)."""
    with open(os.path.join(snapshot_path, "meta.json")) as f:
        md = json.load(f)
    _check_jax_version(md, snapshot_path)
    state = _restore_arrays(os.path.join(snapshot_path, "state.npz"),
                            state_like)
    return state, md.get("meta", {})
