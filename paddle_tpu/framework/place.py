"""Device placement model.

Capability parity with the reference's ``platform::Place`` tagged union
(reference: paddle/fluid/platform/place.h:1) — but TPU-first: the native
accelerator place is :class:`TPUPlace`, and every place resolves to a JAX
device.  ``CUDAPlace`` is kept as a compatibility alias that resolves to the
accelerator if present (so reference-style scripts run with only a Place
swap, per the north star).
"""
from __future__ import annotations


class Place:
    device_id: int = 0

    def __eq__(self, other):
        return type(self) is type(other) and self.device_id == other.device_id

    def __hash__(self):
        return hash((type(self).__name__, self.device_id))

    def __repr__(self):
        return f"{type(self).__name__}({self.device_id})"

    def jax_device(self):
        raise NotImplementedError


class CPUPlace(Place):
    def __init__(self):
        self.device_id = 0

    def __repr__(self):
        return "CPUPlace"

    def jax_device(self):
        import jax

        return jax.devices("cpu")[0]


class TPUPlace(Place):
    """The accelerator place — `fluid.TPUPlace()` per the north star.
    It resolves to TPU ``device_id`` or raises: never to another chip,
    never to the CPU."""

    def __init__(self, device_id: int = 0):
        self.device_id = int(device_id)

    def jax_device(self):
        import jax

        if not is_compiled_with_tpu():
            raise RuntimeError(
                "TPUPlace requested but JAX's default backend is "
                f"{jax.default_backend()!r}, not a TPU")
        devs = jax.devices()
        if not 0 <= self.device_id < len(devs):
            raise ValueError(
                f"{self!r}: device id out of range, this host has "
                f"{len(devs)} TPU device(s)")
        return devs[self.device_id]


class CUDAPlace(TPUPlace):
    """Compatibility alias: reference scripts using CUDAPlace(0) run on
    the TPU without modification."""


class TPUPinnedPlace(CPUPlace):
    """Host-staging place (reference: CUDAPinnedPlace). On TPU, host staging
    is managed by jax.device_put; this is an API-compat shim."""


CUDAPinnedPlace = TPUPinnedPlace


def is_compiled_with_tpu() -> bool:
    """THE accelerator predicate — places, the flags' "auto" values and
    the Pallas kernels' engage checks all ask this one question: is
    JAX's default backend a TPU?"""
    import jax

    return jax.default_backend() == "tpu"


# Reference API-compat name.
is_compiled_with_cuda = is_compiled_with_tpu


def host_tpu_chips() -> int:
    """TPU chips on this host's PCI bus, by JAX's own sysfs scan.  It
    initialises no backend, so a launcher (or a test that starts a
    native PJRT client) can ask without taking the chip — one process
    holds a chip, and a parent that touched it starves its children."""
    from jax._src import hardware_utils

    return hardware_utils.num_available_tpu_chips_and_device_id()[0]


def _get_paddle_place(place):
    """Normalize str/None/Place to a Place (reference: framework.py helpers)."""
    if place is None:
        # no place named: the first device of JAX's default backend.
        # Entry points that report a device number name TPUPlace(0).
        return TPUPlace(0) if is_compiled_with_tpu() else CPUPlace()
    if isinstance(place, Place):
        return place
    if isinstance(place, str):
        p = place.lower()
        if p == "cpu":
            return CPUPlace()
        if p.startswith(("tpu", "gpu", "cuda", "xpu")):
            idx = p.split(":")[1] if ":" in p else 0
            return TPUPlace(int(idx))
    raise ValueError(f"unknown place: {place!r}")
