"""Device API: ``set_device('tpu')`` is the north-star entry point.

Reference analog: python/paddle/device/ (``set_device('gpu:0')``, Place
objects) over phi DeviceContextPool.  TPU-native: a device is a
``jax.Device``; ``set_device`` selects the default device used by creation
ops (via ``jax.default_device``).  'tpu' means an accelerator jax actually
exposes: on a host without one, or for an index past the last chip,
``set_device`` raises — it never hands back a CPU device or another chip.
"""

from __future__ import annotations

import jax

_current = None  # (kind, index, jax.Device)


def is_compiled_with_tpu() -> bool:
    try:
        return any(d.platform not in ("cpu",) for d in jax.devices())
    except RuntimeError:
        return False


# API-compat shims (reference: paddle.is_compiled_with_cuda etc.)
def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_xpu() -> bool:
    return False


def is_compiled_with_rocm() -> bool:
    return False


def is_compiled_with_custom_device(name: str) -> bool:
    return name == "tpu"


def cuda_device_count() -> int:
    return 0


def tpu_device_count() -> int:
    return len([d for d in jax.devices() if d.platform != "cpu"]) or 0


class Place:
    """Lightweight Place (reference: phi::Place / CPUPlace / CUDAPlace)."""

    def __init__(self, kind: str, index: int = 0):
        self.kind = kind
        self.index = index

    def __repr__(self):
        return f"Place({self.kind}:{self.index})"

    def __eq__(self, other):
        return isinstance(other, Place) and (self.kind, self.index) == (other.kind, other.index)

    def jax_device(self):
        if self.kind == "cpu":
            devs = jax.devices("cpu")
        else:
            devs = [d for d in jax.devices() if d.platform != "cpu"]
            if not devs:
                raise RuntimeError(
                    f"{self!r}: jax exposes no accelerator on this host "
                    f"(default backend {jax.default_backend()!r})")
        if not 0 <= self.index < len(devs):
            raise ValueError(
                f"{self!r}: this host has {len(devs)} {self.kind} device(s)")
        return devs[self.index]


def CPUPlace():
    return Place("cpu", 0)


def TPUPlace(index: int = 0):
    return Place("tpu", index)


def set_device(device: str):
    """Select the default device: 'tpu', 'tpu:0', 'cpu'.

    'gpu' is accepted and mapped to the accelerator for script portability
    (one-line migration from the reference), with a warning.
    """
    global _current
    import warnings

    kind, _, idx = device.partition(":")
    index = int(idx) if idx else 0
    if kind == "gpu":
        warnings.warn("set_device('gpu') mapped to 'tpu' on this build")
        kind = "tpu"
    if kind not in ("tpu", "cpu"):
        raise ValueError(f"unsupported device {device!r}; use 'tpu[:i]' or 'cpu'")
    place = Place(kind, index)
    dev = place.jax_device()
    _current = (kind, index, dev)
    jax.config.update("jax_default_device", dev)
    return place


def get_device() -> str:
    if _current is None:
        return "tpu:0" if is_compiled_with_tpu() else "cpu"
    return f"{_current[0]}:{_current[1]}"


def get_default_jax_device():
    if _current is not None:
        return _current[2]
    return None
