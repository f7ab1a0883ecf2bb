"""One cache root for everything this program compiles.

Two caches live under it: JAX's persistent compilation cache (the root
itself) and the AOT executable cache of ``ops/aot_cache`` (``<root>/exec``).
Where ``JAX_COMPILATION_CACHE_DIR`` is set, that directory is the root and
no code names another; where it is not, the root is ``.cache/`` in the
checkout (git-ignored) — a fixed path, because the path is part of JAX's
cache key and a directory that moves never hits.  ``COMETBFT_TPU_EXEC_CACHE``
still redirects the executable cache alone (tests).

Every entry point that may compile calls ``enable()`` once, before its
first compile: the node's start (``cmd/main.py``), ``chip_smoke.py``,
the benchmark's cells, ``scripts/*`` and ``tests/conftest.py``.  Free of
jax imports unless jax is already loaded — a CPU node that never verifies
on a device must not pay a backend import to learn where its cache lives.
"""

from __future__ import annotations

import os
import sys

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
DEFAULT_ROOT = os.path.join(_CHECKOUT, ".cache")

# compiles cheaper than this are not worth a disk round trip
_MIN_COMPILE_SECS = 2.0


def root() -> str:
    """Read at call time, so a process (or a test) that sets the variable
    late is still honoured by the executable cache."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_ROOT


def exec_cache_dir() -> str:
    return os.environ.get("COMETBFT_TPU_EXEC_CACHE") or os.path.join(
        root(), "exec"
    )


def enable() -> str:
    """Turn JAX's persistent compilation cache on under ``root()`` for this
    process and the children that inherit its environment; returns the
    root.  Idempotent."""
    d = os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", DEFAULT_ROOT)
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
    os.environ.setdefault(
        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", str(_MIN_COMPILE_SECS)
    )
    jax = sys.modules.get("jax")
    if jax is not None:
        # jax snapshots these variables when it is imported: a process
        # that imported it first gets the same values at the config level
        jax.config.update("jax_compilation_cache_dir", d)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs", _MIN_COMPILE_SECS
        )
    return d
