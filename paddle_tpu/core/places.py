"""Device places.

Reference: platform/place.h:26-81 defines Place =
variant<CUDAPlace, CPUPlace, CUDAPinnedPlace>; kernels are selected per
place. Here a Place simply selects a JAX backend + device ordinal — all
kernel selection is XLA's job.
"""

from __future__ import annotations

import functools


class Place:
    """Base device identity."""

    _backend = None  # jax platform name

    def __init__(self, device_id: int = 0):
        self.device_id = int(device_id)

    def __eq__(self, other):
        return type(self) is type(other) and self.device_id == other.device_id

    def __hash__(self):
        return hash((type(self).__name__, self.device_id))

    def __repr__(self):
        return f"{type(self).__name__}({self.device_id})"

    def jax_device(self):
        """Resolve to a concrete jax.Device of this place's backend (the
        default backend when the class names none). An ordinal the
        backend does not have is an error, never wrapped onto another
        device."""
        import jax

        devs = jax.devices(self._backend)
        if not 0 <= self.device_id < len(devs):
            raise ValueError(
                f"{self!r}: the {devs[0].platform} backend has "
                f"{len(devs)} device(s)")
        return devs[self.device_id]


class CPUPlace(Place):
    _backend = "cpu"

    def __init__(self):
        super().__init__(0)


class TPUPlace(Place):
    """The native target: a device of jax's default backend — the TPU
    where one is attached, the CPU under JAX_PLATFORMS=cpu (tests).
    Code that must not run without a chip asserts the platform itself
    (chip_smoke.py, benchmark/run.py)."""

    _backend = None  # jax's default backend

    def __init__(self, device_id: int = 0):
        super().__init__(device_id)


class CUDAPlace(Place):
    """API-compatibility alias (reference platform/place.h CUDAPlace).

    Accepted so reference user code runs unchanged; maps to the default
    accelerator (TPU here).
    """

    _backend = None

    def __init__(self, device_id: int = 0):
        super().__init__(device_id)


class CUDAPinnedPlace(CPUPlace):
    pass


@functools.lru_cache(maxsize=None)
def _platform() -> str:
    import jax

    return jax.default_backend()


def is_compiled_with_tpu() -> bool:
    return _platform() == "tpu"


def is_compiled_with_cuda() -> bool:
    # Reference-API shim (framework.py is_compiled_with_cuda): answers
    # "is there an accelerator"; used by user code to pick a place.
    return _platform() != "cpu"
