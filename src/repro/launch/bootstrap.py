"""Process bootstrap: the compile cache, and the jax CPU "fleet" size.

``setup_compile_cache`` places JAX's persistent compilation cache; every
entry point calls it before its first compile (see its docstring).

``ensure_host_devices`` sizes the CPU host platform BEFORE jax starts.

The fleet-sharding layer (``repro.core.shard``) partitions the K axis over
``jax.device_count()`` devices. On CPU that count is 1 unless the process
was started with ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` —
and XLA reads the flag once, when the backend starts (the first device
query), so setting it after that is a silent no-op.
Same story for tcmalloc: ``LD_PRELOAD`` only takes effect at process start.
Hence this module's contract: import it and call ``ensure_host_devices``
FIRST, before any jax import anywhere in the process; when the environment
is missing it re-execs the interpreter once with the right env and the
marker ``REPRO_LAUNCH_BOOTSTRAPPED=1`` (so a misconfigured child can never
re-exec forever).

On a TPU host none of that applies: the chips are the devices, and a
re-exec would start a second process that needs the chip the first one
holds. ``ensure_host_devices`` then only checks the chip count.

Typical use, first lines of a benchmark / experiment entry point::

    from repro.launch.bootstrap import ensure_host_devices
    ensure_host_devices(8)      # may os.execv() and not return
    import jax                  # now sees 8 CPU devices

or purely declarative (print the env for a shell wrapper)::

    python -m repro.launch.bootstrap --shards 8
"""

from __future__ import annotations

import os
import sys
from typing import Dict, Optional

# Re-exec guard: present in the child environment so a host that cannot
# satisfy the request fails loudly instead of exec-looping.
_MARKER = "REPRO_LAUNCH_BOOTSTRAPPED"

_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# The checkout root (this file is src/repro/launch/bootstrap.py).
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))

_DEVICE_FLAG = "--xla_force_host_platform_device_count"

# Common tcmalloc locations (Debian/Ubuntu multiarch, RHEL, conda).
_TCMALLOC_CANDIDATES = (
    "/usr/lib/x86_64-linux-gnu/libtcmalloc.so.4",
    "/usr/lib/x86_64-linux-gnu/libtcmalloc_minimal.so.4",
    "/usr/lib/aarch64-linux-gnu/libtcmalloc.so.4",
    "/usr/lib64/libtcmalloc.so.4",
    "/usr/lib/libtcmalloc.so.4",
)


def compile_cache_dir() -> Optional[str]:
    """The directory ``setup_compile_cache`` gives JAX: None when
    ``JAX_COMPILATION_CACHE_DIR`` is set (JAX reads that variable itself),
    else the fixed ``.jax_cache/`` at the checkout root. The path is part
    of every cache key, so it never depends on a pid, the time or a
    temporary directory."""
    if os.environ.get(_CACHE_ENV):
        return None
    return os.path.join(_CHECKOUT, ".jax_cache")


def setup_compile_cache() -> Optional[str]:
    """Turn on JAX's persistent compilation cache at ``compile_cache_dir``
    (nothing is set when the environment already places it). Call before
    the first compile; returns the directory set, or None."""
    path = compile_cache_dir()
    if path is not None:
        import jax

        jax.config.update("jax_compilation_cache_dir", path)
    return path


def _cpu_platform_forced() -> bool:
    """``JAX_PLATFORMS`` names platforms and none of them is a TPU."""
    plats = os.environ.get("JAX_PLATFORMS", "")
    return bool(plats) and "tpu" not in plats.split(",")


def find_tcmalloc() -> Optional[str]:
    """Path of a preloadable tcmalloc, or None. glibc malloc serializes
    the multi-hundred-MB host buffer churn of a many-device CPU platform;
    tcmalloc's thread caches remove that contention (the HomebrewNLP CPU
    recipe). Optional — sharding works without it, just slower."""
    if os.environ.get("REPRO_NO_TCMALLOC"):
        return None
    for cand in _TCMALLOC_CANDIDATES:
        if os.path.exists(cand):
            return cand
    return None


def host_platform_env(num_shards: int,
                      tcmalloc: bool = True) -> Dict[str, str]:
    """The env vars a process needs for an ``num_shards``-device host
    platform: ``XLA_FLAGS`` with the device-count flag folded into any
    existing flags, plus ``LD_PRELOAD`` of tcmalloc when available."""
    n = int(num_shards)
    if n < 1:
        raise ValueError(f"num_shards must be >= 1, got {num_shards}")
    flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
             if not f.startswith(f"{_DEVICE_FLAG}=")]
    flags.append(f"{_DEVICE_FLAG}={n}")
    env = {"XLA_FLAGS": " ".join(flags)}
    if tcmalloc:
        lib = find_tcmalloc()
        if lib is not None:
            pre = os.environ.get("LD_PRELOAD", "")
            if lib not in pre.split(":"):
                env["LD_PRELOAD"] = f"{pre}:{lib}".strip(":")
    return env


def _current_device_flag() -> Optional[int]:
    for f in os.environ.get("XLA_FLAGS", "").split():
        if f.startswith(f"{_DEVICE_FLAG}="):
            try:
                return int(f.split("=", 1)[1])
            except ValueError:
                return None
    return None


def ensure_host_devices(num_shards: int, tcmalloc: bool = True) -> bool:
    """Make sure this process runs with >= ``num_shards`` devices.

    Returns True when the environment already satisfies the request (also
    covers real multi-device backends, and num_shards <= 1). Unless
    ``JAX_PLATFORMS`` pins a non-TPU platform, the backend starts here:
    on a TPU the chips must suffice (RuntimeError otherwise) and nothing
    is re-exec'd. On the CPU platform this re-execs the CURRENT interpreter
    with ``host_platform_env`` applied — the call does not return in that
    case; if the CPU backend already started with too few devices, raises
    RuntimeError instead of silently mis-sharding.
    """
    n = int(num_shards)
    if n <= 1:
        return True
    flag = _current_device_flag()
    if flag is not None and flag >= n:
        return True
    imported_before = "jax" in sys.modules
    if imported_before or not _cpu_platform_forced():
        import jax

        if jax.device_count() >= n:
            return True
        if jax.default_backend() != "cpu":
            raise RuntimeError(
                f"need {n} devices but this {jax.default_backend()} host "
                f"has {jax.device_count()}")
        if imported_before:
            raise RuntimeError(
                f"need {n} devices but jax initialized with "
                f"{jax.device_count()}; call ensure_host_devices() before "
                "importing jax (or launch with "
                f"XLA_FLAGS={_DEVICE_FLAG}={n})")
        # Only the CPU backend started here, and it holds no chip: the
        # re-exec below restarts it with the forced device count.
    if os.environ.get(_MARKER):
        raise RuntimeError(
            f"bootstrap re-exec did not produce {n} host devices "
            f"(XLA_FLAGS={os.environ.get('XLA_FLAGS', '')!r})")
    env = dict(os.environ)
    env.update(host_platform_env(n, tcmalloc=tcmalloc))
    env[_MARKER] = "1"
    sys.stdout.flush()
    sys.stderr.flush()
    os.execve(sys.executable, [sys.executable] + sys.argv, env)
    raise AssertionError("unreachable: execve returned")  # pragma: no cover


def main(argv=None) -> None:
    """Print ``export`` lines for a shell wrapper (no jax import here)."""
    import argparse

    ap = argparse.ArgumentParser(
        prog="python -m repro.launch.bootstrap",
        description="print the env needed for an N-device host platform")
    ap.add_argument("--shards", type=int, required=True)
    ap.add_argument("--no-tcmalloc", action="store_true")
    args = ap.parse_args(argv)
    for k, v in host_platform_env(args.shards,
                                  tcmalloc=not args.no_tcmalloc).items():
        print(f"export {k}={v!r}")


if __name__ == "__main__":
    main(sys.argv[1:])
