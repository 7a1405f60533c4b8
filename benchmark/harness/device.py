"""The chips a run may use, as JAX reports them. The measuring path has no
CPU fallback: no accelerator, or fewer chips than the cell asks for, is an
error (``--rehearse`` alone lifts the platform check, and such a run prints
no device metric)."""

from __future__ import annotations

from typing import List


class NoChipError(RuntimeError):
    pass


def take_devices(chips: int, rehearse: bool = False) -> List:
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" and not rehearse:
        raise NoChipError(
            f"JAX found no accelerator (platform {devices[0].platform!r}); "
            "the benchmark measures on the chip only")
    if len(devices) < chips:
        raise NoChipError(f"the cell needs {chips} chips, JAX found "
                          f"{len(devices)}: {devices}")
    return list(devices[:chips])


def describe(devices: List) -> dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def memory_peak_bytes(devices: List) -> int:
    """Peak bytes held on the fullest chip (0 where the backend reports
    none, as the CPU does): the allocator's peak of live buffers plus, where
    the runtime keeps a compiled program's scratch space apart as
    "reserved" bytes (the TPU runtime does: a ResNet50 step's 4.4 GB of
    temporaries show only there), the peak of that."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return peak
