"""Persistent AOT-executable store — the "image registry" for unikernel executors.

Built on ``jax.experimental.serialize_executable``: a compiled executable serializes
to bytes once at deploy time; a cold start deserializes it in milliseconds instead of
re-tracing + re-running XLA (the hundreds-of-ms-to-seconds path the paper attributes
to Docker's layered stack).

Layout on disk (content-addressed by FunctionSpec.cache_key):

    <root>/<key>/program.bin     pickled (serialized_executable, in_tree, out_tree)
    <root>/<key>/manifest.json   ImageManifest

Also places JAX's persistent compilation cache (:func:`use_checkout_compile_cache`)
and exposes :func:`enable_xla_disk_cache`, which caches every compile there — the
``cold_jit_cached`` (gVisor-tier) path: still re-traces, but the XLA compile
itself becomes a disk hit — and :func:`persistent_cache_off`, which keeps the
``cold_jit`` tier's compiles out of it.

Invariants: ``put_compiled`` publishes atomically (a concurrent reader sees
the old blob or the new one, never a torn write); payload bytes are immutable
once published under a key — the host program tiers and peer transfers rely
on byte-identical content per key.
"""
from __future__ import annotations

import contextlib
import os
import pickle
import shutil
import threading
from pathlib import Path
from typing import Callable

import jax
from jax.experimental import serialize_executable as _se
from jax.experimental.compilation_cache import compilation_cache as _jax_cc

from repro.core.artifact import ImageManifest

# Streamed boots split the serve program into an AOT head (prefill + first
# token) and tail (the decode scan). The sub-programs live in the same cache
# under derived keys — '#' can't appear in a FunctionSpec.cache_key, so the
# derived keys never collide with a real image.
HEAD_SUFFIX = "#head"
TAIL_SUFFIX = "#tail"
# Continuous-batching decode bundle: the admit program (prefill one request
# into its reserved pages) and the step program (one token for every resident
# slot), both fixed-shape per deployment.
DECODE_ADMIT_SUFFIX = "#decode_admit"
DECODE_STEP_SUFFIX = "#decode_step"


def head_key(key: str) -> str:
    return key + HEAD_SUFFIX


def tail_key(key: str) -> str:
    return key + TAIL_SUFFIX


def decode_admit_key(key: str) -> str:
    return key + DECODE_ADMIT_SUFFIX


def decode_step_key(key: str) -> str:
    return key + DECODE_STEP_SUFFIX


class CompileCache:
    def __init__(self, root: str | Path) -> None:
        self.root = Path(root)
        self.root.mkdir(parents=True, exist_ok=True)
        self._lock = threading.Lock()

    # ------------------------------------------------------------------ paths
    def _dir(self, key: str) -> Path:
        return self.root / key

    def program_path(self, key: str) -> Path:
        return self._dir(key) / "program.bin"

    def manifest_path(self, key: str) -> Path:
        return self._dir(key) / "manifest.json"

    # -------------------------------------------------------------------- api
    def has(self, key: str) -> bool:
        return self.program_path(key).exists()

    def has_split(self, key: str) -> bool:
        """True when both head/tail sub-programs were published for ``key``."""
        return self.has(head_key(key)) and self.has(tail_key(key))

    def put_compiled(self, key: str, compiled) -> int:
        """Serialize a jax.stages.Compiled; returns stored size in bytes."""
        blob = _se.serialize(compiled)                 # (bytes, in_tree, out_tree)
        payload = pickle.dumps(blob)
        d = self._dir(key)
        d.mkdir(parents=True, exist_ok=True)
        tmp = self.program_path(key).with_suffix(".tmp")
        tmp.write_bytes(payload)
        os.replace(tmp, self.program_path(key))        # atomic publish
        return len(payload)

    def read_program_bytes(self, key: str) -> bytes:
        """Fetch the serialized payload only (the boot pipeline's FetchProgram)."""
        return self.program_path(key).read_bytes()

    @staticmethod
    def deserialize_program(payload: bytes) -> Callable:
        """Payload -> loaded executable (the boot pipeline's DeserializeProgram)."""
        blob = pickle.loads(payload)
        return _se.deserialize_and_load(*blob)

    def load_program(self, key: str) -> Callable:
        """Deserialize into a callable executable — the unikernel 'boot'."""
        return self.deserialize_program(self.read_program_bytes(key))

    def load_program_async(self, key: str):
        """Fetch + deserialize on a background thread; returns a Future.

        Lets a caller overlap program acquisition with snapshot weight loading
        without going through the full BootEngine.
        """
        from repro.core.boot import spawn_future
        return spawn_future(lambda: self.load_program(key),
                            name=f"compilecache-load-{key[:12]}")

    def put_manifest(self, key: str, manifest: ImageManifest) -> None:
        self.manifest_path(key).write_text(manifest.to_json())

    def load_manifest(self, key: str) -> ImageManifest:
        return ImageManifest.from_json(self.manifest_path(key).read_text())

    def program_bytes(self, key: str) -> int:
        return self.program_path(key).stat().st_size

    def evict(self, key: str) -> None:
        shutil.rmtree(self._dir(key), ignore_errors=True)

    def keys(self):
        return sorted(p.name for p in self.root.iterdir() if p.is_dir())


CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_checkout_compile_cache() -> str:
    """Place JAX's persistent compilation cache; entry points call it first.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
    is set here. Otherwise the cache lives at a fixed path inside the checkout
    (``CHECKOUT_CACHE_DIR``, ignored by git): the directory is part of each
    entry's key, so a path built per run would never hit. Returns the
    directory in use.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    CHECKOUT_CACHE_DIR.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)


_DISK_CACHE_FLAGS = ("jax_enable_compilation_cache",
                     "jax_persistent_cache_min_compile_time_secs",
                     "jax_persistent_cache_min_entry_size_bytes")


def _disk_cache_flags() -> tuple:
    return tuple(getattr(jax.config, name) for name in _DISK_CACHE_FLAGS)


def _set_disk_cache_flags(values: tuple) -> tuple:
    """Set ``_DISK_CACHE_FLAGS`` and return what they were.

    JAX decides on the first compile whether the persistent cache is in use
    and keeps that answer until ``reset_cache()``, so a flag changed without
    the reset would not reach the next compile.
    """
    previous = _disk_cache_flags()
    for name, value in zip(_DISK_CACHE_FLAGS, values):
        jax.config.update(name, value)
    _jax_cc.reset_cache()
    return previous


def enable_xla_disk_cache() -> tuple:
    """Cache every compile on disk (the gVisor-tier cold path), in the
    directory ``use_checkout_compile_cache`` places. Returns the settings it
    replaced, for ``disable_xla_disk_cache``."""
    use_checkout_compile_cache()
    return _set_disk_cache_flags((True, 0.0, 0))


def disable_xla_disk_cache(previous: tuple) -> None:
    """Restore the settings ``enable_xla_disk_cache`` replaced."""
    _set_disk_cache_flags(previous)


_off_lock = threading.Lock()
_off_depth = 0
_off_saved: tuple = ()


@contextlib.contextmanager
def persistent_cache_off():
    """Compile inside with JAX's persistent cache neither read nor written —
    the ``cold_jit`` tier's full recompile.

    The switch is process-wide: a compile on another thread inside the window
    misses the cache too (slower, never wrong). Windows may overlap; the last
    one out restores the settings the first one found.
    """
    global _off_depth, _off_saved
    with _off_lock:
        if _off_depth == 0:
            _off_saved = _set_disk_cache_flags((False,) + _disk_cache_flags()[1:])
        _off_depth += 1
    try:
        yield
    finally:
        with _off_lock:
            _off_depth -= 1
            if _off_depth == 0:
                _set_disk_cache_flags(_off_saved)


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def refuse_degrade_on_tpu(what: str, err: BaseException) -> None:
    """On a TPU, raise (from ``err``) instead of degrading to an in-process
    or fused program; elsewhere return and let the caller degrade.

    The degrades exist for XLA:CPU's AOT loader, which can refuse executables
    compiled for other machine features. On a TPU a refused or unverified
    artifact is a fault, and serving around it would hide the device path.
    """
    if on_tpu():
        raise RuntimeError(f"{what} failed on the TPU") from err
