"""Per-device-type tuned kernel configs (block sizes / chunk lengths).

The three Pallas entry points (``flash_attention``, ``decode_attention``,
``mlstm_scan``) historically hardcoded their tiling (block_q=block_k=128,
block_c=512, chunk=64).  The autotuner (``repro.autotune``) sweeps those
knobs per device type and persists winners in a CostDB; this module is the
tiny runtime side of that loop: ops.py entry points resolve unspecified
tiling knobs through ``tuned_config`` instead of baking constants in.

Kept import-light on purpose — kernels must not depend on the autotune
package (autotune imports kernels).  The table is populated either by
``repro.autotune.load_tuned_defaults(db)`` at startup or directly via
``register_tuned``.  With no registration, the historical defaults apply
unchanged, so behavior without a CostDB is identical to before.
"""
from __future__ import annotations

import contextlib
from typing import Dict, Iterator, Optional

# Fallbacks = the historical hardcoded values, per kernel knob.
BUILTIN_DEFAULTS: Dict[str, Dict[str, int]] = {
    "flash_attention": {"block_q": 128, "block_k": 128},
    "decode_attention": {"block_c": 512},
    "ssm_scan": {"chunk": 64},
    # consumed by serve.kv_cache when sizing the paged pool (the page IS
    # the kernel tile, so the knob lives with the cache, not the call)
    "paged_attention": {"page_size": 128},
}

# (device_type, kernel) -> {knob: value}
_TUNED: Dict[tuple, Dict[str, int]] = {}

# jax device_kind strings -> the DeviceProfile names used by the CostDB.
_DEVICE_KIND_TO_PROFILE = {
    "TPU v5e": "TPUv5e",
    "TPU v5 lite": "TPUv5e",
    "TPU v5p": "TPUv5p",
    "TPU v5": "TPUv5p",
}

_DEVICE_TYPE_OVERRIDE: Optional[str] = None


def current_device_type() -> Optional[str]:
    """Profile name of the local accelerator, or None when unknown (CPU)."""
    if _DEVICE_TYPE_OVERRIDE is not None:
        return _DEVICE_TYPE_OVERRIDE
    import jax
    kind = jax.devices()[0].device_kind
    if kind in _DEVICE_KIND_TO_PROFILE:
        return _DEVICE_KIND_TO_PROFILE[kind]
    for prefix, name in _DEVICE_KIND_TO_PROFILE.items():
        if kind.startswith(prefix):
            return name
    return None


@contextlib.contextmanager
def override_device_type(name: Optional[str]) -> Iterator[None]:
    """Pretend the local accelerator is ``name`` (tests / CPU dry-runs)."""
    global _DEVICE_TYPE_OVERRIDE
    prev = _DEVICE_TYPE_OVERRIDE
    _DEVICE_TYPE_OVERRIDE = name
    try:
        yield
    finally:
        _DEVICE_TYPE_OVERRIDE = prev


def register_tuned(device_type: str, kernel: str,
                   config: Dict[str, int]) -> None:
    """Install tuned knobs for (device_type, kernel); unknown knobs for the
    kernel are rejected so a stale CostDB can't silently misconfigure."""
    known = BUILTIN_DEFAULTS.get(kernel)
    if known is None:
        raise KeyError(f"unknown kernel {kernel!r}; "
                       f"tunable: {sorted(BUILTIN_DEFAULTS)}")
    bad = set(config) - set(known)
    if bad:
        raise KeyError(f"unknown knobs {sorted(bad)} for kernel {kernel!r}; "
                       f"tunable: {sorted(known)}")
    _TUNED[(device_type, kernel)] = {k: int(v) for k, v in config.items()}


def clear_tuned() -> None:
    _TUNED.clear()


def tuned_config(kernel: str,
                 device_type: Optional[str] = None) -> Dict[str, int]:
    """Effective knobs for ``kernel`` on the local (or given) device type:
    builtin defaults overlaid with any registered tuned values."""
    out = dict(BUILTIN_DEFAULTS[kernel])
    dt = device_type if device_type is not None else current_device_type()
    if dt is not None:
        out.update(_TUNED.get((dt, kernel), {}))
    return out


def resolve(kernel: str, knob: str, value: Optional[int]) -> int:
    """ops.py helper: an explicitly-passed value wins; None consults the
    tuned table (falling back to the historical default)."""
    if value is not None:
        return int(value)
    return tuned_config(kernel)[knob]
