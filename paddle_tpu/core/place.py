"""Device places.

Fluid's ``Place`` variant (``platform/place.h:26-79``) selects which kernel
library runs each op. Here a Place just picks the JAX backend/device; XLA owns
everything below. ``TPUPlace`` is the headline device — the framework's reason
to exist — with ``CPUPlace`` for tests and host-side work.
"""

from __future__ import annotations

from typing import Optional

import jax

__all__ = ["CPUPlace", "TPUPlace", "CUDAPinnedPlace", "Place", "get_device", "is_compiled_with_tpu"]


class Place:
    device_id = 0

    def jax_device(self) -> Optional[jax.Device]:
        return None

    def __eq__(self, other):
        return type(self) is type(other) and self.device_id == other.device_id

    def __hash__(self):
        return hash((type(self).__name__, self.device_id))

    def __repr__(self):
        return "%s(%d)" % (type(self).__name__, self.device_id)


class CPUPlace(Place):
    def __init__(self):
        self.device_id = 0

    def jax_device(self):
        try:
            return jax.devices("cpu")[0]
        except RuntimeError:
            return None


class TPUPlace(Place):
    """The TPU device (north-star equivalent of CUDAPlace place.h:37).

    Resolves to a TPU or fails: constructing it where JAX finds no
    accelerator raises, naming what was found. A CPU device stands in only
    when the process was told to run there (``JAX_PLATFORMS=cpu``, as the
    tests, ``__graft_entry__.py`` and the CPU smokes do), so a trainer
    never lands on the host without a word."""

    def __init__(self, device_id: int = 0):
        self.device_id = device_id
        self.jax_device()

    def jax_device(self):
        devs = _accelerator_devices()
        if not 0 <= self.device_id < len(devs):
            raise ValueError("%r: JAX has %d such device(s): %s"
                             % (self, len(devs), devs))
        return devs[self.device_id]


class CUDAPinnedPlace(Place):
    """Host staging place; on TPU this is just host memory (API parity only)."""


def _accelerator_devices():
    devs = jax.devices()
    accel = [d for d in devs if d.platform != "cpu"]
    if accel:
        return accel
    if (jax.config.jax_platforms or "").strip().lower() == "cpu":
        return devs
    raise RuntimeError(
        "TPUPlace: JAX found no accelerator, only %s. Run where the TPU is "
        "attached, or set JAX_PLATFORMS=cpu to run on the CPU on purpose."
        % (devs,))


def get_device(place: Optional[Place]) -> Optional[jax.Device]:
    if place is None:
        return None
    return place.jax_device()


def is_compiled_with_tpu() -> bool:
    return bool([d for d in jax.devices() if d.platform != "cpu"])
