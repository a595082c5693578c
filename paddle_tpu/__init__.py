"""paddle_tpu — a TPU-native deep learning framework.

Capability class of PaddlePaddle (reference snapshot surveyed in SURVEY.md),
re-designed for TPU: jax.Array storage, XLA compilation, pjit/shard_map
distribution over device meshes, and Pallas kernels for fused ops. The public
API mirrors `paddle.*` (reference: python/paddle/__init__.py) so reference
users can migrate; the implementation shares nothing with the reference's
CUDA/C++ architecture.
"""
from __future__ import annotations

__version__ = "0.1.0"

# Integer-dtype contract: paddle's default integer dtype is int64
# (reference: python/paddle/tensor/creation.py to_tensor — int lists become
# int64). jax disables 64-bit types by default and silently truncates, which
# would give users silent 32-bit wraparound. We enable x64 so int64 is real;
# float defaults remain float32 because every creation op passes an explicit
# dtype (get_default_dtype()). See MIGRATION.md "Integer dtypes".
import jax as _jax  # noqa: E402

_jax.config.update("jax_enable_x64", True)

from .core.dtype import (  # noqa: F401
    float16, bfloat16, float32, float64, int8, int16, int32, int64,
    uint8, uint16, uint32, uint64, bool_, complex64, complex128,
    float8_e4m3fn, float8_e5m2,
    set_default_dtype, get_default_dtype, convert_dtype,
)
from .core.tensor import Tensor, Parameter, to_tensor  # noqa: F401
from .core.autograd import no_grad, enable_grad, grad, is_grad_enabled, set_grad_enabled  # noqa: F401
from .core.random import seed  # noqa: F401
from .core import ops as _ops
from . import linalg, fft, signal  # noqa: F401

# Re-export the whole op surface at top level, paddle-style.
_OP_EXPORTS = [
    n for n in dir(_ops)
    if not n.startswith("_") and callable(getattr(_ops, n))
    and n not in ("Tensor", "apply_op", "to_tensor", "partial", "lax", "convert_dtype",
                  "get_default_dtype", "linalg", "fft")
]
for _n in _OP_EXPORTS:
    globals()[_n] = getattr(_ops, _n)
del _n

from . import nn  # noqa: F401,E402
from . import optimizer  # noqa: F401,E402
from . import io  # noqa: F401,E402
from . import amp  # noqa: F401,E402
from . import jit  # noqa: F401,E402
from . import metric  # noqa: F401,E402
from . import distributed  # noqa: F401,E402
from . import autograd  # noqa: F401,E402
from . import framework  # noqa: F401,E402
from . import device  # noqa: F401,E402
from . import vision  # noqa: F401,E402
from . import distribution  # noqa: F401,E402
from . import hapi  # noqa: F401,E402
from . import incubate  # noqa: F401,E402
from .hapi import Model, summary  # noqa: F401,E402
from .framework.io import save, load  # noqa: F401,E402
from .tensor import tensor as _tensor_ns  # noqa: F401,E402
from . import static  # noqa: F401,E402
from .static.program import enable_static, disable_static  # noqa: F401,E402
from . import inference  # noqa: F401,E402
from . import profiler  # noqa: F401,E402
from . import obs  # noqa: F401,E402
from . import debugging  # noqa: F401,E402
from . import analysis  # noqa: F401,E402
from . import resilience  # noqa: F401,E402
from . import sparse  # noqa: F401,E402
from . import quantization  # noqa: F401,E402
from . import text  # noqa: F401,E402
from . import audio  # noqa: F401,E402
from .core.flags import set_flags, get_flags  # noqa: F401,E402
from . import onnx  # noqa: F401,E402
from . import utils  # noqa: F401,E402
from . import fluid  # noqa: F401,E402
version = type("version", (), {"full_version": __version__,
                               "commit": "unknown",
                               "show": staticmethod(lambda: print(__version__))})


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_xpu() -> bool:
    return False


def is_compiled_with_tpu() -> bool:
    from .device import on_tpu
    return on_tpu()


def in_dynamic_mode() -> bool:
    from .jit.api import _in_jit_trace
    from .static.program import in_static_mode
    return not _in_jit_trace() and not in_static_mode()


def set_device(device: str):
    from .device import set_device as _sd
    return _sd(device)


def get_device() -> str:
    from .device import get_device as _gd
    return _gd()


# ---------------------------------------------------------------------------
# Top-level surface completion (reference python/paddle/__init__.py __all__):
# places, attrs, RNG state, and small framework utilities.

from .fluid import (  # noqa: E402,F401
    CPUPlace, CUDAPlace, CUDAPinnedPlace, NPUPlace, XPUPlace, ParamAttr)
from .distributed import DataParallel  # noqa: E402,F401

bool = bool_  # noqa: A001  — paddle.bool dtype alias
dtype = __import__("numpy").dtype  # paddle.dtype(x) — dtype constructor


def iinfo(dtype):  # noqa: A002
    import numpy as _np
    from .core.dtype import convert_dtype as _cd
    return _np.iinfo(_cd(dtype))


def finfo(dtype):  # noqa: A002
    import numpy as _np
    from .core.dtype import convert_dtype as _cd
    return _np.finfo(_cd(dtype))


def get_rng_state():
    """reference: paddle.get_rng_state — opaque generator state blob."""
    from .core import random as _r
    return _r.get_state()


def set_rng_state(state):
    from .core import random as _r
    return _r.set_state(state)


# single-accelerator runtime: the device RNG *is* the host-threaded threefry
get_cuda_rng_state = get_rng_state
set_cuda_rng_state = set_rng_state


def set_printoptions(precision=None, threshold=None, edgeitems=None,
                     sci_mode=None, linewidth=None):
    """reference: paddle.set_printoptions — Tensor repr goes through numpy."""
    import numpy as _np
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
    _np.set_printoptions(**kw)


def create_parameter(shape, dtype="float32", name=None, attr=None,
                     is_bias=False, default_initializer=None):
    """reference: paddle.create_parameter (fluid/layers/tensor.py)."""
    from .nn import initializer as I
    init = default_initializer or (
        I.Constant(0.0) if is_bias else I.XavierNormal())
    data = init(list(shape), dtype)
    p = Parameter(data._data if isinstance(data, Tensor) else data)
    if name:
        p.name = name
    return p


class LazyGuard:
    """reference: paddle.LazyGuard — defers parameter materialization.
    Here parameters are host numpy/jax arrays materialized on first device
    use by XLA anyway, so the guard only needs to be a scope marker."""

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def disable_signal_handler():
    """reference: paddle.disable_signal_handler — no native signal handlers
    are installed in this runtime; compat no-op."""


def check_shape(shape):
    """reference: input-shape validator used by creation APIs."""
    for s in (shape.tolist() if isinstance(shape, Tensor) else list(shape)):
        if int(s) < -1:
            raise ValueError(f"invalid dim {s} in shape {shape}")


def batch(reader, batch_size, drop_last=False):
    """reference: paddle.batch (legacy reader decorator)."""
    def batched():
        buf = []
        for item in reader():
            buf.append(item)
            if len(buf) == batch_size:
                yield buf
                buf = []
        if buf and not drop_last:
            yield buf
    return batched


def flops(net, input_size, custom_ops=None, print_detail=False):
    """reference: paddle.flops (hapi/dynamic_flops.py) — per-layer FLOPs
    estimate via a forward pass with hooks."""
    import numpy as _np
    from .nn.layer import Layer
    from .nn.layers.common import Linear
    from .nn.layers.conv import Conv2D

    total = [0]

    def count(layer, x, y):
        if isinstance(layer, Linear):
            rows = x[0].size // x[0].shape[-1]
            total[0] += 2 * rows * layer.weight.shape[0] * layer.weight.shape[1]
        elif isinstance(layer, Conv2D):
            # 2 * (Cin/groups * kh * kw) MACs per output element
            k = int(_np.prod(layer.weight.shape[1:]))
            total[0] += 2 * k * int(_np.prod(y.shape))
        return None

    hooks = []
    for sub in net.sublayers(include_self=True):
        hooks.append(sub.register_forward_post_hook(count))
    x = randn(list(input_size))
    was_training = net.training
    net.eval()
    net(x)
    if was_training:
        net.train()
    for h in hooks:
        h.remove()
    if print_detail:
        print(f"Total FLOPs: {total[0]}")
    return total[0]
