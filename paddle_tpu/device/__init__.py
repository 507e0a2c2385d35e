"""Device abstraction.

TPU-native equivalent of the reference's Place/Backend layer
(reference: paddle/phi/common/place.h, paddle/phi/common/backend.h:40,
python/paddle/device/). Instead of a DeviceContext pool with hand-managed
streams, JAX/XLA owns per-device execution; this layer provides device
identity (`Place`), enumeration, selection and placement utilities with the
reference's Python API surface (`set_device`, `get_device`, `is_compiled_with_*`).
"""

from __future__ import annotations
from ..enforce import InvalidArgumentError

import functools
from typing import List, Optional, Union

import jax

__all__ = [
    "Place", "CPUPlace", "TPUPlace", "XPUPlace", "CUDAPlace", "CustomPlace",
    "set_device", "get_device", "get_all_device_type", "device_count",
    "is_compiled_with_cuda", "is_compiled_with_xpu", "is_compiled_with_tpu",
    "get_default_device", "jax_device", "synchronize",
    "register_custom_device", "get_all_custom_device_type",
    "custom_device_count", "load_plugins",
]

class Place:
    """Device identity: (device_type, device_id)."""

    device_type = "unknown"

    def __init__(self, device_id: int = 0):
        self.device_id = int(device_id)

    def __repr__(self):
        return f"Place({self.device_type}:{self.device_id})"

    def __eq__(self, other):
        return (
            isinstance(other, Place)
            and self.device_type == other.device_type
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def jax_device(self):
        devs = [d for d in jax.devices() if _platform_matches(d.platform, self.device_type)]
        if not devs:
            raise RuntimeError(f"No {self.device_type} devices visible to JAX")
        return devs[self.device_id % len(devs)]


class CPUPlace(Place):
    device_type = "cpu"


class TPUPlace(Place):
    device_type = "tpu"


class XPUPlace(Place):
    device_type = "xpu"


class CUDAPlace(Place):
    # Compat alias: on this framework "gpu" requests resolve to the accelerator
    # backend if present (reference users porting scripts keep working).
    device_type = "gpu"


# plugin imports AFTER Place: CustomPlace subclasses it
from .plugin import (CustomPlace, custom_device_count,  # noqa: E402
                     get_all_custom_device_type, load_plugins,
                     register_custom_device)


def _platform_matches(platform: str, device_type: str) -> bool:
    # "gpu"/"xpu" are the reference's accelerator names: ported scripts
    # that ask for them get the TPU — the one accelerator installed —
    # and nothing else does.
    if device_type in ("gpu", "xpu"):
        device_type = "tpu"
    return platform.lower() == device_type


_current_device: List[Optional[str]] = [None]


def get_all_device_type() -> List[str]:
    return sorted({d.platform.lower() for d in jax.devices()})


def device_count(device_type: Optional[str] = None) -> int:
    if device_type is None:
        return jax.device_count()
    return len([d for d in jax.devices() if _platform_matches(d.platform, device_type)])


def set_device(device: Union[str, Place]) -> Place:
    """paddle.set_device equivalent: 'tpu', 'tpu:0', 'cpu', 'gpu:1'."""
    if isinstance(device, Place):
        place = device
    else:
        parts = device.split(":")
        dtype_, idx = parts[0], int(parts[1]) if len(parts) > 1 else 0
        cls = {"cpu": CPUPlace, "tpu": TPUPlace, "xpu": XPUPlace, "gpu": CUDAPlace}.get(dtype_)
        if cls is not None:
            place = cls(idx)
        elif dtype_ in get_all_custom_device_type():
            place = CustomPlace(dtype_, idx)
        else:
            raise InvalidArgumentError(f"Unknown device type: {dtype_}",
                                       op="set_device")
    _current_device[0] = f"{place.device_type}:{place.device_id}"
    return place


def get_device() -> str:
    if _current_device[0] is None:
        return "tpu:0" if is_compiled_with_tpu() else "cpu"
    return _current_device[0]


def get_default_device() -> Place:
    name = get_device()
    parts = name.split(":")
    if parts[0] in get_all_custom_device_type():
        return CustomPlace(parts[0], int(parts[1]) if len(parts) > 1 else 0)
    cls = {"cpu": CPUPlace, "tpu": TPUPlace, "xpu": XPUPlace, "gpu": CUDAPlace}[parts[0]]
    return cls(int(parts[1]) if len(parts) > 1 else 0)


def jax_device(place: Optional[Union[str, Place]] = None):
    """Resolve a Place (or current device) to a concrete jax.Device."""
    if place is None:
        place = get_default_device()
    elif isinstance(place, str):
        saved = _current_device[0]
        try:
            place = set_device(place)
        finally:
            _current_device[0] = saved
    return place.jax_device()


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_xpu() -> bool:
    return False


@functools.lru_cache(maxsize=None)
def is_compiled_with_tpu() -> bool:
    return any(d.platform == "tpu" for d in jax.devices())


def synchronize(place=None):
    """Block until all dispatched work on the device is complete."""
    (jax.device_put(0.0, jax_device(place)) + 0).block_until_ready()


def force_virtual_cpu_devices(n: int = 8) -> None:
    """Force the host CPU platform with `n` virtual devices (the reference's
    subprocess-spawn distributed-test pattern, SURVEY §4, mapped to
    ``--xla_force_host_platform_device_count``). Must run before any jax
    computation initializes the backend. Used by tests/conftest.py and the
    driver's ``dryrun_multichip`` so multi-chip shardings validate without
    real chips. Does not permanently alter JAX_PLATFORMS for child processes
    beyond what the CPU run needs."""
    import os

    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}")
    os.environ["JAX_PLATFORMS"] = "cpu"
    try:
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", n)
    except RuntimeError:
        pass  # backend already initialized: the env vars above stand

from . import streams  # noqa: F401
from .streams import (Event, Stream, current_stream,  # noqa: F401
                      stream_guard)
# NOTE: NOT importing streams.synchronize — the place-aware synchronize()
# defined above is the public one (streams delegates to it).
