"""Places: where tensors live.

Parity with the reference ``Place`` variant
(/root/reference/paddle/fluid/platform/place.h:79) mapped to JAX devices.
``TPUPlace(i)`` plays the role of ``CUDAPlace(i)``; ``CPUPlace`` is the host.
The DeviceContext/stream machinery of the reference
(platform/device_context.h) has no analogue -- XLA owns streams -- so a Place
here is just a device handle plus helpers.
"""

import jax


class Place:
    """Base class for device places."""

    _device_kind = None  # jax platform string

    def __init__(self, device_id=0):
        self.device_id = int(device_id)

    def jax_device(self):
        """Resolve to a concrete jax.Device (None = jax default)."""
        if self._device_kind is None:
            return None
        # raises RuntimeError on a host without that platform: a
        # TPUPlace never quietly resolves to a CPU device
        devs = jax.devices(self._device_kind)
        return devs[self.device_id % len(devs)]

    def __eq__(self, other):
        return type(self) is type(other) and self.device_id == other.device_id

    def __hash__(self):
        return hash((type(self).__name__, self.device_id))

    def __repr__(self):
        return f"{type(self).__name__}({self.device_id})"


class CPUPlace(Place):
    _device_kind = "cpu"

    def __init__(self):
        super().__init__(0)


class TPUPlace(Place):
    _device_kind = "tpu"


# Alias for scripts written against the reference's API surface.
CUDAPlace = TPUPlace


class CUDAPinnedPlace(CPUPlace):
    """Host-pinned memory has no distinct meaning under JAX; alias of CPU."""


def default_place():
    """Accelerator if present, else CPU — analogue of is_compiled_with_cuda checks."""
    backend = jax.default_backend()
    if backend == "cpu":
        return CPUPlace()
    return TPUPlace(0)


def is_compiled_with_tpu():
    return any(d.platform == "tpu" for d in jax.devices())


def device_count():
    return jax.device_count()
