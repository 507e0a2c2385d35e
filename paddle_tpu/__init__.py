"""paddle_tpu: a TPU-native deep-learning framework.

Brand-new framework with the capabilities of the PaddlePaddle reference
(ForFishes/Paddle @ 2024-10-24, /root/reference), re-designed TPU-first:

* compute path: jax.numpy/lax compositions + Pallas kernels, compiled by XLA
  onto the MXU/VPU (replaces PHI's per-backend CUDA kernel registry);
* autodiff: jax.grad over pure functions (replaces the eager GradNode tape);
* distributed: one `jax.sharding.Mesh` + sharding annotations + XLA
  collectives over ICI/DCN (replaces ProcessGroupNCCL/streams);
* capture: jax.jit tracing (replaces dy2static / PIR program capture).

The public API mirrors the reference's `paddle.*` surface so users can port.
"""

import time as _time

_IMPORT_T0 = _time.perf_counter()   # `startup_import` opens (see the end)

from . import utils  # noqa: F401,E402
from . import dtypes  # noqa: F401
from .dtypes import *  # noqa: F401,F403
from . import flags as _flags_mod  # noqa: F401
from .flags import get_flags, set_flags  # noqa: F401
from . import device  # noqa: F401
from .device import (CPUPlace, CUDAPlace, TPUPlace, XPUPlace,  # noqa: F401
                     get_device, set_device, is_compiled_with_cuda,
                     is_compiled_with_tpu, is_compiled_with_xpu)
from .random import get_rng_state, seed, set_rng_state, rng_guard  # noqa: F401
from . import tensor  # noqa: F401
from .framework.selected_rows import SelectedRows  # noqa: F401
from . import linalg  # noqa: F401
from . import fft  # noqa: F401
from . import strings  # noqa: F401
from . import enforce  # noqa: F401
from .tensor import *  # noqa: F401,F403
from .tensor import Tensor  # noqa: F401
from . import nn  # noqa: F401
from .nn.layer.layers import Parameter  # noqa: F401
from . import optimizer  # noqa: F401
from . import ops  # noqa: F401
from . import kernels  # noqa: F401  (registers Pallas fast paths)
from . import incubate  # noqa: F401
from . import amp  # noqa: F401
from . import io  # noqa: F401
from . import audio  # noqa: F401
from . import autograd  # noqa: F401
from . import decomposition  # noqa: F401
from . import geometric  # noqa: F401
from . import onnx  # noqa: F401
from . import signal  # noqa: F401
from . import distribution  # noqa: F401
from . import sparse  # noqa: F401
from . import quantization  # noqa: F401
from . import inference  # noqa: F401
from . import jit  # noqa: F401
from . import metric  # noqa: F401
from . import profiler  # noqa: F401
from . import static  # noqa: F401
from . import text  # noqa: F401
from . import vision  # noqa: F401
from .hapi.model import Model  # noqa: F401

__version__ = "0.1.0"


def grad(func, argnums=0, has_aux=False):
    """Functional gradient (the framework's autodiff entrypoint)."""
    import jax
    return jax.grad(func, argnums=argnums, has_aux=has_aux)


def no_grad(func=None):
    """Compat shim: gradients are explicit (jax.grad), so no_grad is a no-op
    context; provided so ported reference code runs unchanged."""
    import contextlib

    if func is not None and callable(func):
        return func

    @contextlib.contextmanager
    def _ctx():
        yield

    return _ctx()


def is_grad_enabled():
    return True


def set_grad_enabled(mode):
    import contextlib

    @contextlib.contextmanager
    def _ctx():
        yield

    return _ctx()


def stop_gradient(x):
    import jax
    return jax.lax.stop_gradient(x)


# save/load (framework/io.py) are imported lazily to avoid cycles
def save(obj, path, **kwargs):
    from .framework.io import save as _save
    return _save(obj, path, **kwargs)


def load(path, **kwargs):
    from .framework.io import load as _load
    return _load(path, **kwargs)


def summary(net, input_size=None, dtypes=None):
    from .hapi.summary import summary as _summary
    return _summary(net, input_size, dtypes)


# `startup_import` closes: this file's first line to here, on the clock of
# every span (observability/startup.py keeps the record)
from .observability import startup as _startup  # noqa: E402

_startup.note_import(_time.perf_counter())
