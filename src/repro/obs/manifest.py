"""Run manifests (DESIGN.md §14): one JSON document that says what ran.

A manifest pins everything needed to interpret (or re-run) a result file
found on disk months later: the config and its hash (the SAME
``checkpoint.config_hash`` the snapshot sidecars record, so a manifest and
a checkpoint from one run cross-check), the strategy name, the jax/python
versions, the git sha of the working tree, the device/mesh topology, the
communication ledger, the flat buffer's kernel geometry (``d``, ``n_pad``,
``block_rows``), the fault-model and wireless-scenario configurations, and
the structured event stream (divergence rollbacks) the run produced.

``sim.run_experiment`` emits one alongside durable checkpoints
(``<checkpoint_dir>/manifest.json``) and next to a file-backed metric sink
(``<sink>.manifest.json``).
"""
from __future__ import annotations

import dataclasses
import datetime
import json
import os
import platform
import subprocess
from typing import Optional

import jax

MANIFEST_NAME = "manifest.json"


def git_sha(cwd: Optional[str] = None) -> Optional[str]:
    """Best-effort git sha of the source tree (None outside a checkout)."""
    try:
        out = subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            timeout=5,
            cwd=cwd or os.path.dirname(os.path.abspath(__file__)))
        return out.stdout.strip() or None if out.returncode == 0 else None
    except (OSError, subprocess.SubprocessError):
        return None


def device_topology() -> dict:
    """The visible device/mesh topology, host-side."""
    devs = jax.devices()
    return {"platform": devs[0].platform if devs else "none",
            "device_count": jax.device_count(),
            "local_device_count": jax.local_device_count(),
            "process_count": jax.process_count(),
            "devices": [str(d) for d in devs]}


def build_manifest(cfg=None, *, strategy: Optional[str] = None,
                   rounds: Optional[int] = None,
                   n_clients: Optional[int] = None, ledger=None,
                   faults=None, channel=None, events=None, mesh=None,
                   flat: Optional[dict] = None,
                   extra: Optional[dict] = None) -> dict:
    """Assemble a run manifest dict. Everything is optional so partial
    emitters (benchmarks) reuse the same provenance block."""
    from repro.checkpoint.checkpoint import config_hash

    md = {"created_at": datetime.datetime.now(
              datetime.timezone.utc).isoformat(),
          "jax_version": jax.__version__,
          "python_version": platform.python_version(),
          "git_sha": git_sha(),
          "topology": device_topology()}
    if cfg is not None:
        md["config_hash"] = config_hash(cfg)
        md["config"] = (dataclasses.asdict(cfg)
                        if dataclasses.is_dataclass(cfg) else dict(cfg))
    if strategy is not None:
        md["strategy"] = strategy
    if rounds is not None:
        md["rounds"] = int(rounds)
    if n_clients is not None:
        md["n_clients"] = int(n_clients)
    if ledger is not None:
        md["comms"] = ledger.manifest()
    if faults is not None:
        md["faults"] = faults.describe()
    if channel is not None:
        md["channel"] = channel.describe()
    if mesh is not None:
        md["mesh"] = {"axes": dict(zip(mesh.axis_names, mesh.devices.shape)),
                      "devices": [str(d) for d in mesh.devices.ravel()]}
    if flat is not None:
        md["flat_geometry"] = flat
    md["events"] = [dict(e) for e in (events or [])]
    if extra:
        md.update(extra)
    return md


def write_manifest(path: str, manifest: dict) -> str:
    """Write a manifest dict as JSON. ``path`` may be a directory (the
    manifest lands as ``manifest.json`` inside it) or a full file path.
    Returns the file path written."""
    if os.path.isdir(path) or path.endswith(os.sep):
        os.makedirs(path, exist_ok=True)
        path = os.path.join(path, MANIFEST_NAME)
    else:
        parent = os.path.dirname(os.path.abspath(path))
        os.makedirs(parent, exist_ok=True)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(manifest, f, indent=1, default=str)
    os.replace(tmp, path)
    return path


def read_manifest(path: str) -> dict:
    """Read a manifest written by ``write_manifest`` (file or dir path)."""
    if os.path.isdir(path):
        path = os.path.join(path, MANIFEST_NAME)
    with open(path) as f:
        return json.load(f)
