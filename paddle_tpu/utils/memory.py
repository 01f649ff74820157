"""Memory/allocator statistics shim (SURVEY §2.9 #9) + the measured
half of the r15 memory-observability layer.

Reference: paddle/fluid/memory/allocation/allocator_facade.h and the
stat surface behind its FLAGS_fraction_of_gpu_memory_to_use (the
reference's flag; none here).  On TPU the allocator is XLA's BFC — we
expose its PJRT per-device statistics when the backend reports them,
and fall back to an exact census of this client's live device arrays
otherwise (the CPU backend exports no allocator counters).

The live-arrays census is **shard-aware** (r15): a replicated array
contributes its full bytes to every device it lives on, but a
``P('dp')``-sharded array contributes only the shard bytes actually
resident on the queried device — so the census agrees with the static
planner's per-device model (framework/memory_plan.py) across the ZeRO
ladder instead of over-counting sharded state ndev times.

:class:`PeakTracker` is the per-step measured-peak half of the
modeled-vs-measured reconciliation ``tools/mem_report.py`` prints: on
chip it reads ``peak_bytes_in_use`` from the PJRT allocator; on the
CPU proxy it max-tracks the live-arrays census across ``sample()``
calls (a proxy — blind to XLA scratch between samples, which is
exactly why the tool prints both numbers side by side instead of
pretending they are the same quantity).
"""
from __future__ import annotations

from typing import Dict, Optional


def _device_shard_bytes(arr, dev) -> int:
    """Bytes of ``arr`` actually resident on ``dev``: the sum of its
    addressable shards placed there (full nbytes for single-device /
    replicated entries, the row-block for P('dp') layouts)."""
    try:
        shards = arr.addressable_shards
    except Exception:
        shards = None
    if shards:
        total = 0
        for s in shards:
            if s.device == dev:
                total += int(s.data.nbytes)
        return total
    try:
        arr_devs = arr.devices() if callable(getattr(arr, "devices", None)) \
            else {getattr(arr, "device", None)}
    except Exception:
        return 0
    return int(arr.nbytes) if dev in arr_devs else 0


def live_arrays_bytes(device_id: int = 0) -> Dict[str, int]:
    """Shard-aware census of this client's live jax.Arrays on one
    device: exact for framework-held buffers, blind to XLA
    scratch/temporaries."""
    import jax

    devs = jax.devices()
    if device_id >= len(devs):
        raise ValueError(f"device {device_id} not present ({len(devs)} found)")
    dev = devs[device_id]
    total = 0
    count = 0
    for arr in jax.live_arrays():
        b = _device_shard_bytes(arr, dev)
        if b:
            total += b
            count += 1
    return {"bytes_in_use": total, "num_live_arrays": count,
            "source": "live_arrays"}


def memory_stats(device_id: int = 0) -> Dict[str, int]:
    """Allocator statistics for one device.

    Returns a dict with at least ``bytes_in_use`` and ``source``:
    * source="pjrt": the backend's own allocator counters
      (bytes_in_use, peak_bytes_in_use, bytes_limit, ... as reported).
    * source="live_arrays": shard-aware summed bytes of this client's
      live jax.Arrays resident on the device.
    """
    import jax

    devs = jax.devices()
    if device_id >= len(devs):
        raise ValueError(f"device {device_id} not present ({len(devs)} found)")
    dev = devs[device_id]
    stats: Optional[dict] = None
    try:
        stats = dev.memory_stats()
    except Exception:
        stats = None
    if stats:
        out = {k: int(v) for k, v in stats.items()}
        out["source"] = "pjrt"
        return out
    return live_arrays_bytes(device_id)


def measured_peak(device_id: int = 0) -> Dict[str, int]:
    """Best-available measured peak for one device: the PJRT
    allocator's ``peak_bytes_in_use`` on chip, else the CURRENT
    live-arrays census (a floor, not a true peak — use
    :class:`PeakTracker` to max-track it across steps)."""
    s = memory_stats(device_id)
    if s["source"] == "pjrt":
        return {"peak_bytes": int(s.get("peak_bytes_in_use",
                                        s.get("bytes_in_use", 0))),
                "source": "pjrt"}
    return {"peak_bytes": int(s.get("bytes_in_use", 0)),
            "source": "live_arrays"}


class PeakTracker:
    """Per-step measured-peak snapshotter for the modeled-vs-measured
    reconciliation: call :meth:`sample` after each step (and wherever
    else residency may crest); :attr:`peak_bytes` holds the max seen.
    Publishes the ``hbm_measured_peak_bytes`` gauge alongside the
    compile paths' ``hbm_modeled_peak_bytes``."""

    def __init__(self, device_id: int = 0):
        self.device_id = device_id
        self.peak_bytes = 0
        self.samples = 0
        self.source = None

    def sample(self) -> int:
        m = measured_peak(self.device_id)
        self.samples += 1
        self.source = m["source"]
        if m["peak_bytes"] > self.peak_bytes:
            self.peak_bytes = int(m["peak_bytes"])
            from . import telemetry as tm

            tm.gauge("hbm_measured_peak_bytes",
                     "measured per-device HBM peak (pjrt allocator "
                     "counter on chip; live-arrays census max on the "
                     "CPU proxy)").set(self.peak_bytes)
        return self.peak_bytes

    def as_dict(self) -> dict:
        return {"peak_bytes": self.peak_bytes, "samples": self.samples,
                "source": self.source, "device": self.device_id}


def memory_summary(device_id: int = 0) -> str:
    """Human-readable one-liner for logs / the profiler report."""
    s = memory_stats(device_id)
    gb = s.get("bytes_in_use", 0) / (1 << 30)
    if s["source"] == "pjrt":
        peak = s.get("peak_bytes_in_use", 0) / (1 << 30)
        limit = s.get("bytes_limit", 0) / (1 << 30)
        return (f"device {device_id}: {gb:.3f} GiB in use "
                f"(peak {peak:.3f}, limit {limit:.3f}) [pjrt]")
    return (f"device {device_id}: {gb:.3f} GiB across "
            f"{s.get('num_live_arrays', 0)} live arrays [live_arrays]")
