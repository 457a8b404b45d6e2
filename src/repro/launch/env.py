"""Pinned runtime configuration for benchmarks.

Benchmark numbers are only comparable when the process environment is:
allocator churn, XLA log spam, and a surprise host-device count all move
the measured microseconds. ``pin_runtime()`` applies the standard fast
config ONCE, before jax initializes (the exemplar settings production
launchers use):

  - ``LD_PRELOAD`` tcmalloc when the library exists on the host (faster
    malloc for the allocation-heavy staging/packing paths) — applied by
    re-exec'ing the interpreter, since a preload cannot take effect after
    process start. Gated: hosts without tcmalloc simply skip it.
  - ``TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD`` raised so numpy's large
    staging buffers don't spam allocation warnings.
  - ``TF_CPP_MIN_LOG_LEVEL=4`` — no XLA/TSL chatter inside timed regions.
  - optional ``--xla_force_host_platform_device_count=N`` merged into
    ``XLA_FLAGS`` (only BEFORE jax is imported — forcing it later would
    silently not apply, so that is an error).

Import-order contract: call ``pin_runtime()`` before anything imports
jax. ``benchmarks/run.py`` does this on its first line; tests do NOT use
this module (they must see the real single-device CPU host, see
``tests/conftest.py``).

``configure_compile_cache()`` turns on JAX's persistent compilation cache
for the entry points (serving, training, the federated examples, the
benchmark harness, the socket server and ``chip_smoke.py``). The cache key
includes the directory, so the path is fixed: ``JAX_COMPILATION_CACHE_DIR``
when the environment sets it, otherwise ``.jax_cache`` at the root of the
checkout.
"""

from __future__ import annotations

import os
import pathlib
import sys

# <checkout>/src/repro/launch/env.py → <checkout>/.jax_cache
DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"

# re-exec guard: the env var survives the exec, the module global does not.
_REEXEC_MARKER = "REPRO_ENV_PINNED"

_TCMALLOC_CANDIDATES = (
    "/usr/lib/x86_64-linux-gnu/libtcmalloc.so.4",
    "/usr/lib/x86_64-linux-gnu/libtcmalloc_minimal.so.4",
    "/usr/lib/libtcmalloc.so.4",
)


def find_tcmalloc() -> str | None:
    """First tcmalloc shared object present on this host, if any."""
    for path in _TCMALLOC_CANDIDATES:
        if os.path.exists(path):
            return path
    return None


def _merge_xla_flag(flag: str) -> None:
    current = os.environ.get("XLA_FLAGS", "")
    key = flag.split("=", 1)[0]
    if key in current:
        return
    os.environ["XLA_FLAGS"] = f"{current} {flag}".strip()


def pin_runtime(
    *, host_devices: int = 0, tcmalloc: bool = True, reexec: bool = True,
) -> dict:
    """Apply the pinned bench runtime; returns what was applied.

    host_devices > 0 forces the XLA host-platform device count (requires
    jax to not be imported yet). ``tcmalloc=True`` preloads tcmalloc via
    one re-exec when the library exists and we aren't already running
    under it; ``reexec=False`` only reports what would happen.
    """
    applied: dict = {}
    os.environ.setdefault("TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD",
                          "60000000000")
    os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "4")
    applied["tf_log_level"] = os.environ["TF_CPP_MIN_LOG_LEVEL"]

    if host_devices > 0:
        if "jax" in sys.modules:
            raise RuntimeError(
                "pin_runtime(host_devices=...) called after jax was "
                "imported — the device count would silently not apply"
            )
        _merge_xla_flag(
            f"--xla_force_host_platform_device_count={host_devices}"
        )
        applied["host_devices"] = host_devices

    lib = find_tcmalloc() if tcmalloc else None
    applied["tcmalloc"] = lib
    if lib and lib not in os.environ.get("LD_PRELOAD", ""):
        if reexec and not os.environ.get(_REEXEC_MARKER):
            os.environ[_REEXEC_MARKER] = "1"
            preload = os.environ.get("LD_PRELOAD", "")
            os.environ["LD_PRELOAD"] = f"{lib} {preload}".strip()
            sys.stdout.flush()
            sys.stderr.flush()
            os.execv(sys.executable, [sys.executable] + sys.argv)
        applied["tcmalloc"] = None     # present but not preloaded this run
    return applied


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one fixed directory
    and return that directory: ``JAX_COMPILATION_CACHE_DIR`` when set
    (nothing else is configured in code), else ``DEFAULT_CACHE_DIR``."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(DEFAULT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
