"""Computation-environment configuration: the ONE place XLA_FLAGS is set.

Launchers, benchmark parents and their subprocess children all shape the
jax runtime the same three ways — force N host-platform devices, pick a
platform, flip precision/debug switches — and every one of them must do
it BEFORE jax initialises its backend (XLA reads the flags exactly
once). Scattering raw ``os.environ["XLA_FLAGS"] = ...`` assignments
around the tree made that ordering easy to break and the flag strings
easy to drift; this module owns both.

jax itself is imported lazily inside the functions that need it, so the
flag-setting helpers (`force_host_device_count`, `merge_xla_flags`) are
safe to call from a fresh interpreter before any jax import.
"""
from __future__ import annotations

import os
import sys
import warnings
from pathlib import Path

#: JAX reads this variable itself; where it is set, it names the cache.
COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
#: the cache's one fixed home when the variable is unset: inside the
#: checkout (git-ignored), so the path — part of every cache key — never
#: moves between runs.
COMPILE_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def device_count_flag(n: int) -> str:
    """The complete XLA flag forcing ``n`` host-platform devices."""
    return f"--xla_force_host_platform_device_count={int(n)}"


def merge_xla_flags(*flags: str, env: dict | None = None) -> str:
    """Merge ``flags`` into XLA_FLAGS, replacing same-name flags in place.

    Existing flags whose ``--name`` part matches an incoming flag are
    replaced (last write wins); everything else is preserved, so a user's
    own XLA_FLAGS survive a launcher forcing the device count.
    ``env`` defaults to ``os.environ`` — pass a subprocess env dict to
    shape a child without touching this process.
    """
    env = os.environ if env is None else env
    incoming = {f.split("=", 1)[0]: f for f in flags}
    kept = [f for f in env.get("XLA_FLAGS", "").split()
            if f.split("=", 1)[0] not in incoming]
    merged = " ".join(kept + list(incoming.values()))
    env["XLA_FLAGS"] = merged
    return merged


def force_host_device_count(n: int, *, env: dict | None = None) -> None:
    """Force ``n`` host-platform devices (CPU dev meshes / smoke tests).

    Must run before jax initialises its backend; warns (rather than
    silently doing nothing) when a backend already exists in this
    process. With ``env`` given, shapes that dict for a subprocess
    instead — no ordering constraint applies there.
    """
    merge_xla_flags(device_count_flag(n), env=env)
    if env is None and _backend_initialized():
        warnings.warn(
            f"force_host_device_count({n}) after the jax backend "
            f"initialised has no effect; set it before any jax device "
            f"query (or spawn a fresh process)", RuntimeWarning,
            stacklevel=2)


def _backend_initialized() -> bool:
    if "jax" not in sys.modules:
        return False
    try:
        from jax._src import xla_bridge
        return bool(xla_bridge._backends)
    except Exception:       # private API moved — assume not initialised
        return False


def require_devices(n: int, *, local: bool = False) -> None:
    """Fail with the full remedy if fewer than ``n`` devices exist.

    ``local=True`` counts only THIS process's devices (the multihost
    initialiser validates per-process capacity; mesh builders validate
    the global total). Shared by `launch.mesh.make_host_mesh` and
    `launch.mesh.initialize_multihost` so the two error messages cannot
    drift.
    """
    import jax
    have = len(jax.local_devices() if local else jax.devices())
    if have < n:
        scope = "process-local " if local else ""
        raise RuntimeError(
            f"need {n} {scope}devices, have {have}; on a CPU host set "
            f"XLA_FLAGS={device_count_flag(n)} in the environment "
            f"BEFORE jax initialises (or run on a host with enough "
            f"accelerators)")


#: per-platform XLA flag shaping for the kernel launch path. The gpu
#: set follows jax's published performance-tips list; cpu/tpu currently
#: contribute nothing (Mosaic ignores XLA_FLAGS) but keep a slot so a
#: future platform tweak lands in exactly one place.
_KERNEL_FLAGS = {
    "gpu": ("--xla_gpu_triton_gemm_any=True",
            "--xla_gpu_enable_latency_hiding_scheduler=true"),
}


def apply_kernel_flags(platform: str, *, env: dict | None = None) -> str:
    """Shape XLA_FLAGS for kernel launches on ``platform``.

    Called from BOTH ends of the dispatch plane — `set_platform` (the
    launcher side, before jax initialises) and `kernels.plan
    .resolve_plan` (the engine side, when a fit resolves its
    `KernelPlan`) — so the flag set cannot drift between a launcher
    that configured the platform and a bare fit that did not. Merging
    replaces same-name flags in place, so repeated application is
    idempotent and a user's own XLA_FLAGS survive.
    """
    flags = _KERNEL_FLAGS.get(platform, ())
    if flags:
        return merge_xla_flags(*flags, env=env)
    e = os.environ if env is None else env
    return e.get("XLA_FLAGS", "")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already uses it and
    nothing else is set here. Otherwise the cache goes to the fixed
    `COMPILE_CACHE_DIR`. Every compile is kept, however short, so a
    second run of an entry point compiles nothing. Entry points call
    this; tests do not.
    """
    import jax
    path = os.environ.get(COMPILE_CACHE_ENV) or str(COMPILE_CACHE_DIR)
    if not os.environ.get(COMPILE_CACHE_ENV):
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def set_platform(platform: str = "cpu") -> None:
    """Pick the jax platform; also apply its kernel-launch XLA flags.

    Flags are merged (not overwritten) into XLA_FLAGS so a forced host
    device count set earlier survives.
    """
    import jax
    jax.config.update("jax_platform_name", platform)
    apply_kernel_flags(platform)


def jax_enable_x64(use_x64: bool) -> None:
    """Default float precision of jax arrays: 64-bit on/off."""
    import jax
    jax.config.update("jax_enable_x64", bool(use_x64))


def set_debug_nan(flag: bool) -> None:
    """Raise on NaN production (jax debugging flag)."""
    import jax
    jax.config.update("jax_debug_nans", bool(flag))
