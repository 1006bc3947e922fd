"""Where this process's JAX runs, and where its compiles are kept.

Three small facts every device-owning entry point needs and none may
guess at:

* the compile cache is placed from OUTSIDE when the operator says so
  (``JAX_COMPILATION_CACHE_DIR`` — JAX reads it itself, no path is set
  in code), else from the config, else at ONE fixed in-checkout path.
  The directory is part of the cache key, so it is never a temp name,
  a pid or a time;
* a device-owning role serves from the CPU backend only when
  ``JAX_PLATFORMS`` names the CPU first (tests and drills do) —
  "no accelerator found" is otherwise a start-up error, never a quiet
  CPU server;
* a chip belongs to one process: a parent that has initialised an
  accelerator backend must not spawn the child that needs it.

Importing this module does not import JAX.
"""

from __future__ import annotations

import os
import sys
from typing import Optional

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
# Already git-ignored; fixed so every process of a checkout (combined,
# sidecar, bench, scripts) hits what the others compiled.
CHECKOUT_CACHE_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def place_compilation_cache(configured: Optional[str] = None) -> str:
    """Point JAX's persistent compilation cache somewhere stable and
    return the directory in use.  Call before anything compiles."""
    import jax
    # The cache's key leaves an operation's metadata out by default, so
    # a cache that an older build warmed would hand this one programs
    # without the scope names the profile summary counts
    # device time by (utils.profile_summary.STAGES).  With metadata in
    # the key such an entry is never matched.  The price: source lines
    # are metadata too, so an edit to a file a program is traced from
    # makes the next start a cold one (deploy/DEPLOY.md).
    jax.config.update("jax_compilation_cache_include_metadata_in_key",
                      True)
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env        # JAX reads the variable; no path is set
    path = configured or CHECKOUT_CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def cpu_requested() -> bool:
    """Did the environment ask for the CPU backend by name — as the
    platform to run on, i.e. FIRST in ``JAX_PLATFORMS``?  (``tpu,cpu``
    asks for the TPU and merely keeps the CPU backend available.)"""
    platforms = os.environ.get("JAX_PLATFORMS", "")
    return platforms.split(",")[0].strip().lower() == "cpu"


def device_identity(devices=None) -> dict:
    """``{platform, kind, count, ids}`` of the devices this process
    serves from (default: all of ``jax.devices()``), as JAX reports
    them.  Raises when that is the CPU backend and nobody asked for
    it."""
    import jax
    devices = list(jax.devices() if devices is None else devices)
    if not devices:
        raise RuntimeError("JAX reports no devices")
    first = devices[0]
    if first.platform == "cpu" and not cpu_requested():
        raise RuntimeError(
            "no accelerator found: JAX fell back to the CPU backend "
            "and JAX_PLATFORMS does not ask for it — a device-owning "
            "role refuses to serve from the CPU unless told to "
            "(JAX_PLATFORMS=cpu)")
    return {"platform": first.platform, "kind": first.device_kind,
            "count": len(devices),
            "ids": [int(d.id) for d in devices]}


def initialised_jax():
    """The ``jax`` module if this process has initialised a backend,
    else None — asked without importing JAX or initialising one."""
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    from jax._src import xla_bridge
    return jax if xla_bridge.backends_are_initialized() else None


def require_chip_free(what: str) -> None:
    """Refuse to spawn a device-owning child from a process that has
    itself initialised an accelerator backend (it holds the chip; the
    child would fail or hang).  A CPU backend holds nothing."""
    jax = initialised_jax()
    if jax is not None and jax.default_backend() != "cpu":
        raise RuntimeError(
            f"{what}: this process already holds the "
            f"{jax.default_backend()} backend; a chip belongs to one "
            f"process, so the device-owning child cannot have it")
