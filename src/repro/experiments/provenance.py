"""Provenance stamping for experiment artifacts.

Every summary JSON the runner writes answers "what exactly produced
this?" without archaeology: the spec itself (canonical mapping + content
hash), the config file it came from (path + file sha256, when one was
used), the git tree (HEAD sha + dirty bit), the RNG identity (seed and
the engine salt constants — the values that, with the spec, pin every
drawn variate), the backend/device geometry actually seen at run time,
and wall-clock accounting.  Rows carry none of this — a provenance-
stamped regen of a committed baseline stays byte-identical row for row.
"""
from __future__ import annotations

import hashlib
import subprocess
import sys
import time


def file_sha256(path: str) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as fh:
        for chunk in iter(lambda: fh.read(1 << 16), b""):
            h.update(chunk)
    return h.hexdigest()


def git_revision(cwd: str = "."):
    """(sha, dirty) of the enclosing checkout, or (None, None) outside
    one — provenance must never make a run fail."""
    try:
        sha = subprocess.run(
            ["git", "rev-parse", "HEAD"], cwd=cwd, capture_output=True,
            text=True, check=True).stdout.strip()
        dirty = bool(subprocess.run(
            ["git", "status", "--porcelain"], cwd=cwd,
            capture_output=True, text=True, check=True).stdout.strip())
        return sha, dirty
    except (OSError, subprocess.CalledProcessError):
        return None, None


def rng_salts() -> dict:
    """The counter-RNG salt constants that, together with the seed,
    identify every variate stream an experiment draws (ARCHITECTURE
    invariant 1).  Salts are compile-time constants; recording them
    makes a stale artifact detectable if one ever changes."""
    from ..core.client_latency import _KEY_SALT
    from ..core.downtime_batched import _SIZE_SALT
    return {"size": _SIZE_SALT, "key": _KEY_SALT}


def device_geometry() -> dict:
    """Backend platform, device kind and visible device count as jax
    actually sees them (the spec records what was *asked for*; this
    records what the process *got* — e.g. a forced 8-host-device CPU
    mesh, or which TPU generation ran the grid)."""
    try:
        import jax
        return {"platform": jax.default_backend(),
                "device_kind": jax.devices()[0].device_kind,
                "device_count": jax.device_count()}
    except Exception:  # noqa: BLE001 — provenance must never fail a run
        return {"platform": None, "device_kind": None, "device_count": None}


def build_provenance(spec, *, config_path=None, wall_s=None,
                     started_unix=None) -> dict:
    """The ``meta.provenance`` mapping for one run of ``spec``."""
    sha, dirty = git_revision()
    prov = {
        "spec_sha256": spec.content_hash(),
        "config_path": str(config_path) if config_path else None,
        "config_sha256": (file_sha256(config_path)
                          if config_path else None),
        "git_sha": sha,
        "git_dirty": dirty,
        "seed": spec.seed,
        "rng_salts": rng_salts(),
        "requested": {"backend": spec.backend, "devices": spec.devices,
                      "trials": spec.trials},
        "observed": device_geometry(),
        "python": sys.version.split()[0],
        "started_unix": started_unix if started_unix is not None
        else time.time(),
        "wall_s": wall_s,
    }
    return prov
