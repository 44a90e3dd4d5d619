"""The chips a run holds, their description and the peaks table."""
from __future__ import annotations

import json
import os
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def use_compile_cache(root: Path) -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    or at ``$JAX_COMPILATION_CACHE_DIR`` when that is set; every program
    is written to it, however short its compile.  Call before JAX
    compiles anything."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def require_chips(n: int) -> list:
    """The first ``n`` TPU devices; raises :class:`NoChip` otherwise."""
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"JAX found no accelerator: {e}") from e
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found platform {devs[0].platform!r}, not a TPU")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} chips, JAX found {len(devs)}")
    return devs[:n]


def peaks(device_kind: str, path: Path = PEAKS) -> dict:
    """The published peaks of ``device_kind``; unknown kinds are an
    error, never a default."""
    table = json.loads(path.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in {path.name}")
    return table[device_kind]


def describe(devs: list) -> dict:
    """``device`` of the result line: platform, kind, count and the peak
    bytes in use on the fullest chip."""
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}
