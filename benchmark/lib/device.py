"""The device a run is on, and what it holds.

A measuring run needs the TPU and as many chips as its cell asks for; with
fewer, or on another platform, nothing is run and the exit code is not 0.

``device.memory_stats()["peak_bytes_in_use"]`` on this libtpu does not count a
program's temporaries (PR 21: 0.48 GB read after a ResNet-50 step that holds
11.0 GB of them).  The allocator books them as *reserved*: after that step
``peak_bytes_reserved`` read 10.90 GB against the compiler's 10.98 GB (my chip
run, PR 23).  Whether the two peaks fall at the same instant, and whether
reserved bytes hold what is in use as well, no run has shown.  So the peak
reported here is the larger of the two on the fullest chip: never above the
true peak, which is what a floor on memory in use needs.  Both fields are
printed on an earlier line of every run.
"""
from __future__ import annotations

import sys


def require(jax, chips: int, rehearsal: bool):
    devices = jax.devices()
    if rehearsal:
        if len(devices) < chips:
            sys.exit(f"benchmark rehearsal: the cell asks for {chips} "
                     f"device(s), JAX has {len(devices)}")
        return devices[:chips]
    if devices[0].platform != "tpu":
        sys.exit(f"benchmark: needs a TPU, JAX found platform "
                 f"{devices[0].platform!r} ({devices[0].device_kind}); "
                 f"nothing was run")
    if len(devices) < chips:
        sys.exit(f"benchmark: the cell asks for {chips} chip(s), JAX has "
                 f"{len(devices)}; nothing was run")
    return devices[:chips]


def describe(jax) -> dict:
    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


def memory_stats(devices) -> list:
    """Both peaks of every device, as the allocator reports them."""
    return [{k: int((d.memory_stats() or {}).get(k, 0))
             for k in ("peak_bytes_in_use", "peak_bytes_reserved")}
            for d in devices]


def memory_peak_bytes(devices) -> int:
    """``max(peak_bytes_in_use, peak_bytes_reserved)`` of the fullest
    device."""
    return max(max(m.values()) for m in memory_stats(devices))
