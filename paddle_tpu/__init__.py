"""paddle_tpu: a TPU-native deep-learning framework with a paddle-shaped API.

Built from scratch on jax/XLA/Pallas/pjit (see SURVEY.md for the reference
architecture map this replaces).  The compute path is XLA end-to-end: eager
ops dispatch one jnp call each; ``@to_static``/Model.fit trace whole steps
into single fused HLO modules; distribution is mesh + shardings over ICI/DCN.
"""

from __future__ import annotations

import time as _time

_IMPORT_T0 = _time.time()       # phase ``startup.import``: closed at the end

import os as _os  # noqa: E402

import jax as _jax  # noqa: E402

# Importing this package initialises NO backend (tests/test_startup.py): a
# chip belongs to one process, so a parent that only imports paddle_tpu must
# leave it free for the child it starts.  The first array op, paddle.seed'ed
# key draw or set_device() is what claims the device.

# Multi-process contract (SURVEY.md §3.5): the launch CLI exports
# PADDLE_TRAINER_* env vars; jax.distributed.initialize must run BEFORE the
# first backend touch — so join the coordination service here, first thing
# (dependency-free module).
from ._bootstrap import maybe_join_coordination_service as _mpi  # noqa: E402

_mpi()

# int64/float64 semantics parity with the reference (paddle defaults labels
# and index tensors to int64).  Model code stays float32/bf16; f64 on TPU is
# a user error surfaced by XLA, same as the reference on most GPU kernels.
_jax.config.update("jax_enable_x64", True)


def _configure_compile_cache():
    """Persistent XLA compile cache, placeable from outside.

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX's own handling of it stands and
    this code sets no directory.  Unset: one FIXED path inside the checkout
    (``<repo>/.jax_cache``, git-ignored) — the directory is part of what a
    warm restart must find again, so never a temp dir, pid or timestamp."""
    if _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    root = _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))
    _jax.config.update("jax_compilation_cache_dir",
                       _os.path.join(root, ".jax_cache"))


_configure_compile_cache()

__version__ = "0.1.0"

from .framework import dtypes as _dtypes
from .framework.state import get_default_dtype, set_default_dtype  # noqa: F401
from .framework.random import seed, get_rng_state, set_rng_state  # noqa: F401

# dtype aliases: paddle_tpu.float32 etc.
import numpy as _np
import jax.numpy as _jnp

bool = _jnp.bool_  # noqa: A001
uint8 = _jnp.uint8
int8 = _jnp.int8
int16 = _jnp.int16
int32 = _jnp.int32
int64 = _jnp.int64
float16 = _jnp.float16
bfloat16 = _jnp.bfloat16
float32 = _jnp.float32
float64 = _jnp.float64
complex64 = _jnp.complex64
complex128 = _jnp.complex128

from .tensor import *  # noqa: F401,F403 — Tensor, Parameter + full op surface
from .tensor import Tensor, Parameter  # noqa: F401
from .tensor import linalg  # noqa: F401 — paddle.linalg namespace

from .flags import set_flags, get_flags  # noqa: F401
from .device import (  # noqa: F401
    set_device, get_device, is_compiled_with_tpu, is_compiled_with_cuda,
    is_compiled_with_xpu, is_compiled_with_rocm, is_compiled_with_custom_device,
    CPUPlace, TPUPlace, Place,
)

from .autograd import no_grad, enable_grad, set_grad_enabled, grad, is_grad_enabled  # noqa: F401

# subpackages loaded lazily so partial builds stay importable
import importlib as _importlib

_LAZY = ("nn", "optimizer", "amp", "io", "metric", "jit", "static", "vision",
         "distributed", "autograd", "device", "framework", "hapi", "profiler",
         "incubate", "utils", "sparse", "signal", "fft", "text", "ops",
         "distribution", "regularizer", "callbacks", "inference",
         "audio", "version", "quantization", "geometric", "hub", "serving",
         "observability", "resilience")


def __getattr__(name):
    if name in _LAZY:
        mod = _importlib.import_module(f".{name}", __name__)
        globals()[name] = mod
        return mod
    if name == "Model":
        from .hapi import Model as M

        globals()["Model"] = M
        return M
    if name in ("register_op", "load_op_library"):
        from .framework import custom_op as _co

        globals()["register_op"] = _co.register_op
        globals()["load_op_library"] = _co.load_op_library
        return globals()[name]
    if name in ("save", "load"):
        from .framework import io as _io

        globals()["save"], globals()["load"] = _io.save, _io.load
        return globals()[name]
    if name == "DataParallel":
        from .distributed.parallel import DataParallel as DP

        globals()["DataParallel"] = DP
        return DP
    if name == "summary":
        from .hapi import summary as s

        globals()["summary"] = s
        return s
    if name == "flops":
        from .hapi import flops as f

        globals()["flops"] = f
        return f
    if name == "ParamAttr":
        from .nn.param_attr import ParamAttr as PA

        globals()["ParamAttr"] = PA
        return PA
    raise AttributeError(f"module 'paddle_tpu' has no attribute {name!r}")


def disable_static(place=None):
    """API compat: this framework is always 'dygraph by default'."""
    return None


def enable_static():
    from . import static as _static

    _static._STATIC_MODE[0] = True


def in_dynamic_mode():
    from . import static as _static

    return not _static._STATIC_MODE[0]


def get_cudnn_version():
    return None


def device_count():
    import jax

    return len(jax.devices())


def is_grad_enabled():  # re-exported via autograd too
    from .framework import state

    return state.grad_enabled()


def to_tensor(data, dtype=None, place=None, stop_gradient=True):
    from .tensor import creation

    return creation.to_tensor(data, dtype, place, stop_gradient)


def set_printoptions(precision=None, threshold=None, edgeitems=None,
                     sci_mode=None, linewidth=None):
    """reference: paddle.set_printoptions — maps onto numpy's printoptions
    (Tensor repr prints via numpy)."""
    import numpy as _np_

    kw = {}
    if precision is not None:
        kw["precision"] = precision
    if threshold is not None:
        kw["threshold"] = threshold
    if edgeitems is not None:
        kw["edgeitems"] = edgeitems
    if linewidth is not None:
        kw["linewidth"] = linewidth
    if sci_mode is not None:
        kw["suppress"] = not sci_mode
    _np_.set_printoptions(**kw)


def use_deterministic_algorithms(flag=True):
    """reference: paddle.use_deterministic_algorithms.

    XLA:TPU programs are already deterministic for a fixed program+seed, so
    on this backend the call only records the request in the flag registry
    (queryable via get_flags) — there is no runtime knob to flip, and the
    already-initialized backend could not read one anyway."""
    set_flags({"FLAGS_cudnn_deterministic": bool(flag)})


# Last: what this process builds is on record from here on (the build
# listeners register with the module; still no backend), and the import
# itself is the record's first phase.
from .observability import programs as _programs  # noqa: E402

_programs.record().add_phase("startup.import", None, _IMPORT_T0,
                             _time.time() - _IMPORT_T0)
