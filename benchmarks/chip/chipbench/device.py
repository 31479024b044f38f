"""The chip: which one this is, its published peaks, and its memory."""
from __future__ import annotations

from typing import Any

# Published per-chip peaks, keyed by ``jax.Device.device_kind``.
# Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16,
# 16 GB HBM at 819 GB/s). The FLOP/s peak is the MXU's bf16 rate: the
# highest the chip has, so no share computed against it can overstate.
PEAKS: dict[str, dict[str, Any]] = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def peaks_for(device_kind: str) -> dict[str, Any]:
    """The peaks of ``device_kind``; a device missing from the table is an
    error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None


def require_chips(jax: Any, chips: int) -> list[Any]:
    """The first ``chips`` TPU devices; raises :class:`NoChip` otherwise."""
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devices[0].platform!r})")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found "
                     f"{len(devices)}")
    peaks_for(devices[0].device_kind)
    return devices[:chips]


def describe(jax: Any, chips: int) -> dict[str, Any]:
    """The result line's ``device``: platform, kind and count as JAX reports
    them, and the peak memory of the fullest chip used."""
    devices = jax.devices()
    peak = 0
    for d in devices[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}
