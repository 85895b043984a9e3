"""Pinned, reproducible launch environment (DESIGN.md §15).

BENCH numbers are only comparable across machines when the allocator,
the XLA host-device topology and the dtype policy are pinned — the
related launchers (HomebrewNLP-Jax/olmax ``run.sh``, SNIPPETS.md 1-2)
all preload tcmalloc and hard-code their XLA flags for exactly this
reason.  This module is that policy as code, usable two ways:

* ``python -m repro.launch.env --shell`` emits ``export`` lines for
  ``run.sh`` to eval BEFORE the Python process starts (``LD_PRELOAD``
  and ``XLA_FLAGS`` must be set pre-import to take effect) — this path
  deliberately never imports jax;
* :func:`describe_env` snapshots the pinned variables at run time so
  every ``Roofline``/BENCH row records the environment it was measured
  under (an unpinned run is visible in the artifact, not silently
  comparable).

Existing settings are respected: ``pinned_env`` merges its XLA flags
into a caller-provided ``XLA_FLAGS`` (flags already present win) and
only preloads tcmalloc when the library actually exists on the host.
"""
from __future__ import annotations

import os
from typing import Dict, Optional

# candidate tcmalloc locations (Debian/Ubuntu multiarch, RH lib64)
TCMALLOC_PATHS = (
    "/usr/lib/x86_64-linux-gnu/libtcmalloc.so.4",
    "/usr/lib/x86_64-linux-gnu/libtcmalloc_minimal.so.4",
    "/usr/lib64/libtcmalloc.so.4",
    "/usr/lib/libtcmalloc.so.4",
)

# XLA flags every benchmarked run pins (flag name -> value)
XLA_FLAG_DEFAULTS = {
    # deterministic host topology: benches and tests assume 8 local
    # devices regardless of the machine's core count
    "--xla_force_host_platform_device_count": "8",
    # step markers at the entry of each jitted step — profiles and
    # roofline attribution line up across machines (the flag takes the
    # DebugOptions::StepMarkerLocation enum NAME; a bare int aborts XLA)
    "--xla_step_marker_location": "STEP_MARK_AT_ENTRY",
}

ENV_DEFAULTS = {
    # f32 accumulation policy: x32 default types (the repo's numerics
    # contracts — bit-equality, 3e-8 pins — assume f32, not f64)
    "JAX_DEFAULT_DTYPE_BITS": "32",
    # silence TF/XLA C++ banner noise in benchmark logs
    "TF_CPP_MIN_LOG_LEVEL": "4",
    # tcmalloc: only report pathological (>60GB) single allocations
    "TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD": "60000000000",
}

# the variables a BENCH artifact records (measurement provenance)
RECORDED_VARS = ("LD_PRELOAD", "XLA_FLAGS", "JAX_DEFAULT_DTYPE_BITS",
                 "TF_CPP_MIN_LOG_LEVEL", "JAX_PLATFORMS",
                 "REPRO_KERNEL_BACKEND")


def find_tcmalloc() -> Optional[str]:
    for p in TCMALLOC_PATHS:
        if os.path.exists(p):
            return p
    return None


def merge_xla_flags(existing: str, defaults: Dict[str, str]) -> str:
    """Append each default flag unless the caller already set it."""
    parts = existing.split()
    have = {p.split("=", 1)[0] for p in parts}
    for flag, value in defaults.items():
        if flag not in have:
            parts.append(f"{flag}={value}")
    return " ".join(parts)


def pinned_env(base: Optional[Dict[str, str]] = None,
               host_devices: Optional[int] = None) -> Dict[str, str]:
    """The pinned launch environment as a {var: value} delta.

    ``base`` defaults to ``os.environ``; only variables that need to
    change are returned.  Caller-set values win: XLA flags merge, plain
    vars are left alone when already present.
    """
    base = dict(os.environ if base is None else base)
    out: Dict[str, str] = {}
    xla_defaults = dict(XLA_FLAG_DEFAULTS)
    if host_devices is not None:
        xla_defaults["--xla_force_host_platform_device_count"] = str(
            host_devices)
    merged = merge_xla_flags(base.get("XLA_FLAGS", ""), xla_defaults)
    if merged != base.get("XLA_FLAGS", ""):
        out["XLA_FLAGS"] = merged
    for var, value in ENV_DEFAULTS.items():
        if var not in base:
            out[var] = value
    tcmalloc = find_tcmalloc()
    if tcmalloc and tcmalloc not in base.get("LD_PRELOAD", ""):
        preload = base.get("LD_PRELOAD", "")
        out["LD_PRELOAD"] = f"{preload}:{tcmalloc}".strip(":")
    return out


def apply_pinned_env(host_devices: Optional[int] = None) -> Dict[str, str]:
    """Apply :func:`pinned_env` to ``os.environ`` (pre-jax-import only:
    XLA reads these once at backend initialization)."""
    delta = pinned_env(host_devices=host_devices)
    os.environ.update(delta)
    return delta


def describe_env(base: Optional[Dict[str, str]] = None) -> Dict[str, str]:
    """The recorded-variable snapshot stamped into Roofline/BENCH rows."""
    base = os.environ if base is None else base
    return {var: base[var] for var in RECORDED_VARS if var in base}


CACHE_DIR_VAR = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here.  Otherwise the cache lives at ``.jax_cache`` in
    the checkout: a fixed path, since the path is part of every cache
    key.  Called by the launch entry points before their first compile —
    never at import and never from tests.
    """
    path = os.environ.get(CACHE_DIR_VAR)
    if path:
        return path
    import jax

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))
    path = os.path.join(root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def shell_lines(host_devices: Optional[int] = None) -> list:
    """``export`` lines for run.sh (evaluated before Python starts)."""
    return [f"export {var}={value!r}"
            for var, value in sorted(pinned_env(
                host_devices=host_devices).items())]


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--shell", action="store_true",
                    help="emit export lines for eval in run.sh")
    ap.add_argument("--host-devices", type=int, default=None,
                    help="override --xla_force_host_platform_device_count")
    args = ap.parse_args(argv)
    if args.shell:
        for ln in shell_lines(host_devices=args.host_devices):
            print(ln)
    else:
        for var, value in sorted(describe_env().items()):
            print(f"{var}={value}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
