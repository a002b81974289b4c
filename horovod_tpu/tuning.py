"""XLA flag tuning for comm/compute overlap, and compile-cache placement.

The bucketed gradient sync (:mod:`horovod_tpu.ops.overlap`) makes each
bucket's collective *schedulable* inside the backward pass — whether it
actually overlaps is XLA's call. On TPU two compiler features do the
work: **async collective fusion** (collectives split into start/done
pairs that run on the DMA engines while the TensorCore keeps computing)
and the **latency-hiding scheduler** (hoists the starts as early as
their operands allow and sinks the dones as late as their consumers
allow). Both are compiler flags the runtime reads ONCE at backend
initialization — so the knobs must land in the environment before the
first ``jax`` device touch.

:func:`apply_xla_flags` appends the preset idempotently and never
clobbers a flag the user already set (their value wins, even when it
disagrees with the preset). ``HOROVOD_XLA_FLAGS_PRESET=<preset>`` makes
``hvd.init`` apply it automatically before backend init. A flag lands in
the variable its runtime parses — ``--xla_tpu_*`` in
``LIBTPU_INIT_ARGS``, which libtpu reads; in ``XLA_FLAGS`` the same flag
is ``Unknown flags in XLA_FLAGS``, *fatal* at backend init on a CPU
jaxlib and on a TPU host alike — and application is gated on the
resolved target platform: on a CPU host the call is a recorded no-op.
"""

from __future__ import annotations

import importlib.util
import logging
import os
import sys
import warnings
from typing import List, Optional, Tuple

__all__ = [
    "PRESETS",
    "PRESET_ENV",
    "apply_xla_flags",
    "maybe_apply_from_env",
    "backend_initialized",
    "enable_compile_cache",
]

log = logging.getLogger("horovod_tpu")

#: env knob: name of the preset ``hvd.init`` applies before backend init
#: (documented in docs/performance.md's overlap knob table)
PRESET_ENV = "HOROVOD_XLA_FLAGS_PRESET"

#: preset name -> tuple of (flag, platform) pairs. ``platform`` names the
#: backend the flag exists on; flags for other platforms are skipped (a
#: TPU-only flag in XLA_FLAGS is FATAL on a CPU jaxlib).
#: the comm/compute-overlap flag set: async start/done collectives + the
#: latency-hiding scheduler that pins the overlapped schedule
_OVERLAP_FLAGS = (
    ("--xla_tpu_enable_async_collective_fusion=true", "tpu"),
    ("--xla_tpu_enable_async_collective_fusion_fuse_all_gather=true",
     "tpu"),
    ("--xla_tpu_enable_async_collective_fusion_multiple_steps=true",
     "tpu"),
    ("--xla_tpu_enable_latency_hiding_scheduler=true", "tpu"),
)

#: the environment variable each platform's runtime parses its flags from
#: (anything else: XLA_FLAGS)
_FLAGS_VAR = {"tpu": "LIBTPU_INIT_ARGS"}

PRESETS = {
    "overlap": _OVERLAP_FLAGS,
    # the HOROVOD_PALLAS companion: a Pallas kernel is an opaque custom
    # call to XLA's scheduler — without async collectives + the
    # latency-hiding scheduler, a custom call adjacent to a collective
    # SERIALIZES against it, giving back the overlap PR 10 bought. The
    # flag set is therefore exactly the overlap set (no Pallas-specific
    # XLA flags exist to arm); the separate name records intent and
    # keeps the knob table honest. Backend resolution is shared the
    # other way too: pallas_kernels' `auto` mode resolves the target
    # platform through this module's `_target_platform`, so consulting
    # HOROVOD_PALLAS never initializes a backend before these flags land.
    "pallas": _OVERLAP_FLAGS,
    # explicit opt-out spelling for HOROVOD_XLA_FLAGS_PRESET
    "none": (),
}


def backend_initialized() -> bool:
    """Best-effort: has a jax backend already been created (meaning
    XLA_FLAGS edits no longer take effect in this process)?"""
    if "jax" not in sys.modules:
        return False
    try:
        from jax._src import xla_bridge

        return bool(getattr(xla_bridge, "_backends", None))
    except (ImportError, AttributeError):
        return False


def enable_compile_cache() -> Optional[str]:
    """Place JAX's persistent compilation cache; call before the first
    backend touch. Where ``JAX_COMPILATION_CACHE_DIR`` is set jax reads it
    itself and nothing is set here (returns None); otherwise the cache
    lives at ``<checkout>/.jax_cache`` — a fixed path, because the path
    is part of the cache key and a directory that moves never hits."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    import jax

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def _target_platform(env) -> str:
    """The platform the next backend init will target: the explicit
    ``JAX_PLATFORMS``/``JAX_PLATFORM_NAME`` pin when present, else
    ``tpu`` iff a TPU runtime (libtpu) is importable."""
    pins = env.get("JAX_PLATFORMS") or env.get("JAX_PLATFORM_NAME") or ""
    if pins:
        return pins.split(",")[0].strip().lower()
    try:
        has_tpu = importlib.util.find_spec("libtpu") is not None
    except (ImportError, ValueError):
        has_tpu = False
    return "tpu" if has_tpu else "cpu"


def _flag_name(flag: str) -> str:
    return flag.split("=", 1)[0]


def apply_xla_flags(preset: Optional[str] = None, *, env=None,
                    platform: Optional[str] = None,
                    warn_if_late: bool = True
                    ) -> Tuple[List[str], List[str]]:
    """Append the preset's flags to the variable their runtime parses
    (``LIBTPU_INIT_ARGS`` for TPU flags, else ``XLA_FLAGS``), idempotently.

    Returns ``(added, skipped)``: flags appended now, and flags withheld
    because the user already set that flag name (their value wins) or
    the flag's platform does not match the resolved target. Calling
    twice adds nothing the second time. With ``warn_if_late`` a warning
    fires when a flag is added after a backend already initialized —
    the edit then only helps subprocesses.
    """
    env = os.environ if env is None else env
    if preset is None:
        preset = env.get(PRESET_ENV) or "overlap"
    if preset not in PRESETS:
        raise ValueError(
            f"unknown XLA flags preset {preset!r}; known: "
            f"{sorted(PRESETS)}"
        )
    platform = (platform or _target_platform(env)).lower()
    flag_vars = {"XLA_FLAGS", *_FLAGS_VAR.values()}
    present = {_flag_name(t) for var in flag_vars
               for t in env.get(var, "").split()}
    added: List[str] = []
    skipped: List[str] = []
    for flag, flag_platform in PRESETS[preset]:
        if _flag_name(flag) in present:
            skipped.append(flag)      # user-set value wins, always
        elif flag_platform != platform:
            skipped.append(flag)      # TPU-only flag on a CPU jaxlib is
            # a fatal parse error, not a no-op — withhold it
        else:
            added.append(flag)
            var = _FLAGS_VAR.get(flag_platform, "XLA_FLAGS")
            env[var] = f"{env.get(var, '')} {flag}".strip()
    if added:
        if warn_if_late and env is os.environ and backend_initialized():
            warnings.warn(
                "horovod_tpu.tuning.apply_xla_flags ran after a jax "
                "backend initialized; compiler flags are read once at "
                "backend init, so the overlap flags only affect "
                "subprocesses. "
                "Set HOROVOD_XLA_FLAGS_PRESET=overlap (or call "
                "apply_xla_flags) before the first device touch.",
                RuntimeWarning,
                stacklevel=2,
            )
    if skipped:
        log.debug("tuning: withheld XLA flags %s (user-set or platform "
                  "mismatch for %r)", skipped, platform)
    return added, skipped


def maybe_apply_from_env(env=None) -> Tuple[List[str], List[str]]:
    """Apply the ``HOROVOD_XLA_FLAGS_PRESET`` preset when the knob is
    set; no-op otherwise. ``hvd.init`` calls this before its first
    backend touch, so the env knob alone is enough to arm the overlap
    flags on every entry point (launcher children included — the env
    rides through)."""
    env = os.environ if env is None else env
    if not env.get(PRESET_ENV):
        return [], []
    return apply_xla_flags(env.get(PRESET_ENV), env=env)
