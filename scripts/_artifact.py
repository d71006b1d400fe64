"""Shared artifact writer for on-chip evidence JSONs.

Every TPU-evidence artifact the builder commits carries a provenance
block (UTC run time, device string, jax/jaxlib/libtpu versions, git SHA
at run time) so driver-vs-local evidence can be reconciled at a glance.
Versions come from importlib.metadata — this module never imports jax
(a parent that has touched JAX holds the chip); callers that are
already on-chip pass the device string explicitly.

The round tag defaults to r05 and is overridable via DST_ROUND so the
same scripts serve future rounds without edits.
"""

from __future__ import annotations

import datetime
import json
import os
import subprocess

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _round() -> str:
    # read lazily so DST_ROUND set after import (or between calls in one
    # process) is honored — import-time capture burned a dry run once
    return os.environ.get("DST_ROUND", "r05")


def _pkg_version(pkg: str):
    try:
        from importlib.metadata import version

        return version(pkg)
    except Exception:
        return None


def provenance(device: str | None = None) -> dict:
    try:
        sha = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], cwd=HERE,
            capture_output=True, text=True, timeout=10).stdout.strip() or None
    except Exception:
        sha = None
    return {
        "utc": datetime.datetime.now(datetime.timezone.utc)
        .isoformat(timespec="seconds"),
        "device": device,
        "git_sha": sha,
        "jax": _pkg_version("jax"),
        "jaxlib": _pkg_version("jaxlib"),
        "libtpu": _pkg_version("libtpu") or _pkg_version("libtpu-nightly"),
    }


def artifact_path(prefix: str) -> str:
    return os.path.join(HERE, f"{prefix}_{_round()}.json")


def write_artifact(prefix: str, data, device: str | None = None,
                   path: str | None = None,
                   extra: dict | None = None) -> str:
    """Write ``{prefix}_{ROUND}.json`` (or ``path``) atomically with a
    provenance block merged in.

    dict payloads get a ``provenance`` key; list payloads are wrapped as
    ``{"provenance": ..., "data": [...]}`` (consumers index ["data"]).
    ``extra`` adds top-level wrapper fields (e.g. a completeness flag for
    incrementally-written artifacts).
    """
    path = path or artifact_path(prefix)
    if isinstance(data, dict):
        payload = {**data, **(extra or {}), "provenance": provenance(device)}
    else:
        payload = {"provenance": provenance(device), **(extra or {}),
                   "data": data}
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(payload, f, indent=1)
    os.replace(tmp, path)
    return path
