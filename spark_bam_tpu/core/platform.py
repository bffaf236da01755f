"""Process-level jax set-up: the persistent compile cache and the virtual
multi-device CPU platform the tests run on.

Shared by ``tests/conftest.py``, ``__graft_entry__.dryrun_multichip``, the
fabric worker and ``chip_smoke.py`` so each rule lives in exactly one place.

This module must stay importable without pulling in jax at module scope.
"""

import os
import re
from pathlib import Path

_COUNT_FLAG = "--xla_force_host_platform_device_count"

#: Where compiled programs persist when the environment does not say: a
#: fixed path inside the checkout (the path is part of the cache key, so a
#: directory that moves never hits). Listed in ``.gitignore``.
CHECKOUT_JAX_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Place JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR`` is jax's own variable: when it is set jax
    already reads it and nothing is set here. Otherwise every entry point
    (CLI, tests, fabric workers, ``chip_smoke.py``) shares
    ``CHECKOUT_JAX_CACHE``. The first compile of the 32 MiB window program
    takes most of a minute; warm processes load it instead."""
    import jax

    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    CHECKOUT_JAX_CACHE.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_JAX_CACHE))
    return str(CHECKOUT_JAX_CACHE)


def force_cpu_devices(n_devices: int, defer_init: bool = False) -> None:
    """Force jax onto ``n_devices`` virtual CPU devices.

    Must run before any jax backend is initialized (first ``jax.devices()`` /
    first traced computation); after that the host-device-count flag is
    latched and this has no effect.

    ``defer_init=True`` only sets the flags without touching a backend —
    required before ``jax.distributed.initialize()``, which must itself run
    before any backend init (multi-host bring-up, parallel/multihost.py).
    """
    flags = os.environ.get("XLA_FLAGS", "")
    opt = f"{_COUNT_FLAG}={n_devices}"
    if _COUNT_FLAG in flags:
        flags = re.sub(rf"{_COUNT_FLAG}=\d+", opt, flags)
    else:
        flags = f"{flags} {opt}".strip()
    os.environ["XLA_FLAGS"] = flags
    os.environ["JAX_PLATFORMS"] = "cpu"

    try:
        import jax
    except ImportError:
        # Env vars are set; a later jax install in this process still sees
        # them. Callers that need jax will fail at their own import site.
        return

    try:
        jax.config.update("jax_platforms", "cpu")
    except Exception:
        pass

    if defer_init:
        return

    # Initializing here (with our flags set) both latches the virtual-device
    # count and lets us fail loud instead of silently running on another
    # backend that some earlier import already initialized.
    if jax.default_backend() != "cpu" or len(jax.devices("cpu")) < n_devices:
        raise RuntimeError(
            f"force_cpu_devices({n_devices}) too late: a jax backend was "
            f"already initialized (default={jax.default_backend()!r}, "
            f"cpu devices={len(jax.devices('cpu'))}); call it before any "
            "jax.devices()/traced computation, or use a fresh process"
        )
