"""Serialized render executables: XLA compiles that survive the process.

A restart re-traces and re-compiles every serving program — ~20 s per
JPEG program shape when compiled for a v5e, paid in front of live
users.  The persistent trace cache
(``renderer.compilation_cache_dir``) already skips the XLA backend
compile, but still pays tracing + lowering per shape; this cache stores
the COMPILED executable itself via
``jax.experimental.serialize_executable`` so a warm restart loads and
calls it directly — no trace, no lower, no compile.

Keying: a content key over (device fingerprint, entry-point name,
argument signature).  The fingerprint folds jax/jaxlib versions,
backend platform, device kind and device count — a serialized
executable is only valid on the hardware+toolchain that built it, so a
driver upgrade or a different chip reads as a clean miss and the
serving path falls back to the jitted entry point (which still enjoys
the ``compilation_cache_dir`` trace cache when configured).  Loads are
guarded end to end: a corrupt, truncated or foreign file is deleted
and counted, never raised through a render.

Trust model: entries are pickles, exactly like JAX's own persistent
compilation cache artifacts — the directory must be owned by the
service user, not a shared writable path.
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
import pickle
import threading
import time
from typing import Dict, List, Optional

log = logging.getLogger("omero_ms_image_region_tpu.execcache")

_ENVELOPE_VERSION = 1

# Grace before a background capture runs: the AOT lower+compile it
# performs is multi-core work, and the burst that minted the new shape
# deserves the machine first (same posture as the batcher's cost
# estimate capture).
_CAPTURE_DELAY_S = 3.0


def device_fingerprint() -> str:
    """Everything a serialized executable's validity depends on."""
    import jax
    try:
        import jaxlib
        jaxlib_version = getattr(jaxlib, "__version__", "?")
    except Exception:
        jaxlib_version = "?"
    devices = jax.devices()
    return json.dumps({
        "jax": jax.__version__,
        "jaxlib": jaxlib_version,
        "platform": devices[0].platform if devices else "?",
        "device_kind": devices[0].device_kind if devices else "?",
        "device_count": len(devices),
    }, sort_keys=True)


def _leaf_sig(x) -> list:
    if isinstance(x, (bool, int, float, complex)):
        # Python scalars trace weak-typed; their signature is their
        # Python type, not a concrete dtype.
        return ["py", type(x).__name__]
    return [list(getattr(x, "shape", ())), str(x.dtype)]


def args_signature(args) -> str:
    """Stable JSON signature of a call's argument avals (shapes +
    dtypes + tree structure)."""
    import jax
    leaves, treedef = jax.tree_util.tree_flatten(args)
    return json.dumps([[_leaf_sig(leaf) for leaf in leaves],
                       str(treedef)])


def abstractify(args):
    """Concrete call args -> aval-only stand-ins (ShapeDtypeStruct for
    arrays, Python scalars verbatim).  ``lower()`` only needs avals,
    and the background capture must NOT pin a batch-sized staged HBM
    stack for its grace delay + compile — same signature, zero bytes
    referenced."""
    import jax
    import numpy as np

    def leaf(x):
        if isinstance(x, (bool, int, float, complex)):
            return x
        return jax.ShapeDtypeStruct(np.shape(x), x.dtype)

    return jax.tree_util.tree_map(leaf, args)


class ExecutableCache:
    """Disk + memory cache of compiled serving executables.

    ``lookup`` is the hot-path read: in-memory registry first, then (at
    most once per key) a disk deserialize.  ``capture_async`` is the
    write: a one-shot background lower+compile+serialize per key.
    ``ensure`` is the synchronous prewarm form.  All failure modes
    degrade to None/no-op — the jitted entry point always exists.
    """

    def __init__(self, directory: str,
                 capture_delay_s: float = _CAPTURE_DELAY_S):
        self.directory = directory
        self.capture_delay_s = capture_delay_s
        self._lock = threading.Lock()
        self._loaded: Dict[str, object] = {}       # key -> callable
        self._probed: set = set()                  # keys disk-probed
        self._capturing: set = set()               # keys claimed
        self._capture_threads: List[threading.Thread] = []
        self._fingerprint: Optional[str] = None
        self.hits = 0
        self.misses = 0
        self.loaded = 0          # deserialized from disk
        self.saved = 0           # serialized to disk

    # ------------------------------------------------------------- keys

    def fingerprint(self) -> str:
        if self._fingerprint is None:
            self._fingerprint = device_fingerprint()
        return self._fingerprint

    def _key(self, fn_name: str, sig: str) -> str:
        h = hashlib.blake2b(digest_size=16)
        h.update(self.fingerprint().encode())
        h.update(fn_name.encode())
        h.update(sig.encode())
        return h.hexdigest()

    def _path(self, key: str) -> str:
        return os.path.join(self.directory, key + ".jexec")

    # ------------------------------------------------------------ reads

    def lookup(self, fn_name: str, args):
        """The loaded executable for this exact call signature, or None
        (caller falls back to the jitted entry point)."""
        try:
            key = self._key(fn_name, args_signature(args))
        except Exception:
            return None
        with self._lock:
            fn = self._loaded.get(key)
            if fn is not None:
                self.hits += 1
                return fn
            if key in self._probed:
                self.misses += 1
                return None
            self._probed.add(key)
        fn = self._load(key, fn_name)
        with self._lock:
            if fn is not None:
                self._loaded[key] = fn
                self.hits += 1
                self.loaded += 1
            else:
                self.misses += 1
        return fn

    def _load(self, key: str, fn_name: str, env=None):
        """Deserialize one stored executable; any failure (missing,
        corrupt, foreign fingerprint, backend mismatch) is a miss.
        ``env`` passes an already-unpickled envelope (the preload path
        reads each multi-megabyte file exactly once)."""
        path = self._path(key)
        if env is None:
            try:
                with open(path, "rb") as f:
                    env = pickle.load(f)
            except (OSError, EOFError):
                return None
            except Exception:
                log.warning("executable cache entry %s unreadable; "
                            "removing", path)
                self._remove(path)
                return None
        try:
            if (not isinstance(env, dict)
                    or env.get("version") != _ENVELOPE_VERSION
                    or env.get("fingerprint") != self.fingerprint()
                    or env.get("fn") != fn_name):
                return None
            from jax.experimental import serialize_executable
            loaded = serialize_executable.deserialize_and_load(
                env["payload"], env["in_tree"], env["out_tree"])
            from ..utils import telemetry
            telemetry.FLIGHT.record("execcache.load", fn=fn_name)
            return loaded
        except Exception:
            # Deserialization blew up (toolchain drift the fingerprint
            # missed, or hostile bytes): the entry is dead weight.
            log.warning("executable cache entry %s failed to "
                        "deserialize; removing", path, exc_info=True)
            self._remove(path)
            return None

    def _remove(self, path: str) -> None:
        try:
            os.unlink(path)
        except OSError:
            pass

    def invalidate(self, fn_name: str, args) -> None:
        """Drop a loaded executable that failed at CALL time (runtime
        drift the fingerprint cannot see — XLA runtime flags, plugin
        state).  Evicted from memory AND disk, and kept in the probed
        set, so exactly one group pays the failed attempt and the jit
        fallback serves from then on."""
        try:
            key = self._key(fn_name, args_signature(args))
        except Exception:
            return
        with self._lock:
            self._loaded.pop(key, None)
            self._probed.add(key)
        self._remove(self._path(key))
        log.warning("invalidated serialized executable for %s (failed "
                    "at call time); serving on the jit path", fn_name)

    # ----------------------------------------------------------- writes

    def _compile_and_save(self, fn_name: str, jitted_fn, args):
        """Lower+compile the entry point for these args, serialize the
        executable atomically, register it in memory.  Returns the
        compiled callable or None."""
        sig = args_signature(args)
        key = self._key(fn_name, sig)
        try:
            compiled = jitted_fn.lower(*args).compile()
        except Exception:
            log.warning("executable capture compile failed for %s",
                        fn_name, exc_info=True)
            return None
        try:
            from jax.experimental import serialize_executable
            payload, in_tree, out_tree = \
                serialize_executable.serialize(compiled)
            env = {"version": _ENVELOPE_VERSION,
                   "fingerprint": self.fingerprint(),
                   "fn": fn_name, "sig": sig,
                   "payload": payload, "in_tree": in_tree,
                   "out_tree": out_tree}
            os.makedirs(self.directory, exist_ok=True)
            path = self._path(key)
            tmp = path + f".tmp.{os.getpid()}.{threading.get_ident()}"
            with open(tmp, "wb") as f:
                pickle.dump(env, f)
            os.replace(tmp, path)
            with self._lock:
                self.saved += 1
            from ..utils import telemetry
            telemetry.FLIGHT.record("execcache.save", fn=fn_name)
        except Exception:
            # Serialization unsupported on this backend, or the disk
            # refused: the compiled program still serves THIS process.
            log.warning("executable serialize failed for %s (serving "
                        "continues on the in-process program)", fn_name,
                        exc_info=True)
        with self._lock:
            self._loaded[key] = compiled
            self._probed.add(key)
        return compiled

    def ensure(self, fn_name: str, jitted_fn, args):
        """Load-or-compile synchronously (the prewarm path): a stored
        executable deserializes instead of compiling; a fresh one
        compiles once and is serialized for the next life."""
        fn = self.lookup(fn_name, args)
        if fn is not None:
            return fn
        return self._compile_and_save(fn_name, jitted_fn, args)

    def capture_async(self, fn_name: str, jitted_fn, args) -> bool:
        """One-shot background capture for this signature (the serving
        path's write side): claimed atomically so concurrent first
        groups of one shape spawn one capture; runs after a grace
        delay so the burst that minted the shape keeps the cores."""
        try:
            key = self._key(fn_name, args_signature(args))
        except Exception:
            return False
        with self._lock:
            if key in self._capturing or key in self._loaded:
                return False
            self._capturing.add(key)
        # Aval stand-ins, NOT the live batch: the closure must not pin
        # a staged device stack in HBM for the delay + compile window.
        try:
            args = abstractify(args)
        except Exception:
            with self._lock:
                self._capturing.discard(key)
            return False

        def run():
            if self.capture_delay_s > 0:
                time.sleep(self.capture_delay_s)
            self._compile_and_save(fn_name, jitted_fn, args)

        t = threading.Thread(target=run, name=f"exec-capture-{key[:8]}",
                             daemon=True)
        with self._lock:
            self._capture_threads = [
                th for th in self._capture_threads if th.is_alive()]
            self._capture_threads.append(t)
        t.start()
        return True

    def drain(self, timeout_s: float = 30.0) -> None:
        """Join pending captures (shutdown/snapshot/tests)."""
        deadline = time.monotonic() + timeout_s
        with self._lock:
            threads = list(self._capture_threads)
        for t in threads:
            t.join(timeout=max(0.0, deadline - time.monotonic()))

    # ------------------------------------------------------ enumeration

    def stored_keys(self) -> List[str]:
        """Keys present on disk (the warm-state manifest's executable
        ladder)."""
        try:
            return sorted(name[:-len(".jexec")]
                          for name in os.listdir(self.directory)
                          if name.endswith(".jexec"))
        except OSError:
            return []

    def preload(self, keys: List[str]) -> int:
        """Boot rehydrate: deserialize stored executables into the
        in-memory registry so the FIRST group of each shape calls a
        compiled program.  Returns how many loaded; every failure is a
        skip.  The entry's own header carries fn name validation."""
        n = 0
        for key in keys:
            with self._lock:
                if key in self._loaded:
                    continue
            path = self._path(key)
            try:
                with open(path, "rb") as f:
                    env = pickle.load(f)
                fn_name = env.get("fn") if isinstance(env, dict) else None
            except Exception:
                self._remove(path)
                continue
            if not fn_name:
                continue
            # Hand the envelope through: each multi-megabyte payload
            # is read + unpickled exactly once on the boot path.
            fn = self._load(key, fn_name, env=env)
            if fn is not None:
                with self._lock:
                    self._loaded[key] = fn
                    self._probed.add(key)
                    self.loaded += 1
                n += 1
        return n
