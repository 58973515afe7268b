"""paddle_tpu — a TPU-native deep-learning framework with PaddlePaddle's
capabilities, built on JAX/XLA/Pallas (see SURVEY.md for the reference map).

The top-level namespace mirrors `import paddle`: tensor creation/math live here,
`nn`, `optimizer`, `amp`, `io`, `vision`, `distributed`… as submodules.
"""
from __future__ import annotations

import jax as _jax

# float64/int64 parity with the reference requires x64 mode; TPU code paths
# should still use fp32/bf16 (float64 on TPU is software-emulated).
_jax.config.update("jax_enable_x64", True)

from .core import (  # noqa: F401,E402
    Tensor, to_tensor,
    no_grad, enable_grad, grad, is_grad_enabled, set_grad_enabled,
    Place, CPUPlace, TPUPlace, CUDAPlace, CUDAPinnedPlace,
    set_device, get_device,
    is_compiled_with_cuda, is_compiled_with_rocm, is_compiled_with_xpu,
    is_compiled_with_tpu, is_compiled_with_distribute,
    bool_, uint8, int8, int16, int32, int64, float16, bfloat16, float32,
    float64, complex64, complex128, set_default_dtype, get_default_dtype,
    seed, get_rng_state, set_rng_state,
)
from .ops import *  # noqa: F401,F403,E402
from .ops import creation as _creation  # noqa: E402

# submodules (imported lazily below to keep `import paddle_tpu` light where
# possible; nn/optimizer pull in the full layer corpus)
from . import nn  # noqa: E402,F401
from . import optimizer  # noqa: E402,F401
from . import amp  # noqa: E402,F401
from . import io  # noqa: E402,F401
from . import metric  # noqa: E402,F401
from . import framework  # noqa: E402,F401
from .framework.io import save, load  # noqa: E402,F401
from . import device  # noqa: E402,F401
from . import autograd  # noqa: E402,F401
from . import distributed  # noqa: E402,F401
from . import incubate  # noqa: E402,F401
from . import vision  # noqa: E402,F401
from . import hapi  # noqa: E402,F401
from . import jit  # noqa: E402,F401
from . import static  # noqa: E402,F401
from . import inference  # noqa: E402,F401
from . import serving  # noqa: E402,F401
from . import profiler  # noqa: E402,F401
from . import distribution  # noqa: E402,F401
from .flags import get_flags, set_flags  # noqa: E402,F401
from . import text  # noqa: E402,F401
from . import audio  # noqa: E402,F401
from . import sparse  # noqa: E402,F401
from . import utils  # noqa: E402,F401
from . import fft  # noqa: E402,F401
from . import signal  # noqa: E402,F401
from . import geometric  # noqa: E402,F401
from . import quantization  # noqa: E402,F401
from . import hub  # noqa: E402,F401
from . import dataset  # noqa: E402,F401
from . import reader  # noqa: E402,F401
from . import regularizer  # noqa: E402,F401
from . import sysconfig  # noqa: E402,F401
from . import compat  # noqa: E402,F401
from .batch import batch  # noqa: E402,F401
from . import cost_model  # noqa: E402,F401
from . import tensor  # noqa: E402,F401
# `from .ops import *` already bound the name `linalg` to ops.linalg, which
# makes `from . import linalg` a no-op; import the namespace module explicitly
import importlib as _importlib  # noqa: E402

linalg = _importlib.import_module(".linalg", __name__)
from . import onnx  # noqa: E402,F401
from . import observability  # noqa: E402,F401
from . import version  # noqa: E402,F401


def iinfo(dtype):
    import numpy as _np

    from .core.dtype import convert_dtype as _cd
    return _np.iinfo(_cd(dtype))


def finfo(dtype):
    import ml_dtypes as _mld  # handles bfloat16/fp8 plus all numpy floats

    from .core.dtype import convert_dtype as _cd
    return _mld.finfo(_cd(dtype))
from .nn import ParamAttr  # noqa: E402,F401
from .hapi import Model  # noqa: E402,F401
from . import callbacks  # noqa: E402,F401
from .distributed.parallel import DataParallel  # noqa: E402,F401
from .ops.compat_surface import *  # noqa: E402,F401,F403

# remaining reference top-level aliases (paddle/__init__.py __all__)
bool = bool_  # noqa: A001 — the reference exports `paddle.bool`
dtype = type(float32)
VarBase = Tensor                      # legacy eager tensor alias
LazyGuard = None                      # bound below (needs nn)
CustomPlace = IPUPlace = MLUPlace = NPUPlace = XPUPlace = Place
get_cuda_rng_state = get_rng_state    # device-agnostic RNG state here
set_cuda_rng_state = set_rng_state
commit = "unknown"                    # filled by release tooling upstream
full_version = "0.1.0"


def is_compiled_with_cinn() -> bool:  # noqa: A003
    return False


def is_compiled_with_ipu() -> bool:
    return False


def is_compiled_with_mlu() -> bool:
    return False


def is_compiled_with_npu() -> bool:
    return False


def get_cudnn_version():
    """None: no cuDNN in a TPU build (reference returns an int or None)."""
    return None


def disable_signal_handler():
    """No-op: the runtime installs no custom signal handlers to disable
    (the reference unhooks its C++ fault handlers here)."""


class LazyGuard:  # noqa: F811
    """Delayed parameter materialization (reference paddle.LazyGuard) —
    maps onto nn.abstract_init: layers built inside the guard carry
    shape/dtype only until a train step or explicit init materializes
    them."""

    def __enter__(self):
        from .nn import abstract_init
        self._cm = abstract_init()
        self._cm.__enter__()
        return self

    def __exit__(self, *exc):
        return self._cm.__exit__(*exc)

from .core.tensor_methods import install_tensor_methods as _itm  # noqa: E402

_itm()
del _itm

__version__ = "0.1.0"

# `paddle.disable_static()/enable_static()` parity: this framework is always
# "dygraph" at the surface (compiled via jit underneath), so these are no-ops
# kept for source compatibility.
_static_mode = False


def enable_static():
    global _static_mode
    _static_mode = True


def disable_static():
    global _static_mode
    _static_mode = False


def in_dynamic_mode() -> bool:
    return not _static_mode


def is_grad_enabled_():  # legacy alias
    return is_grad_enabled()


def summary(net, input_size=None, dtypes=None):
    from .hapi.summary import summary as _summary
    return _summary(net, input_size, dtypes)


def flops(net, input_size, custom_ops=None, print_detail=False):
    """Forward-pass FLOPs of `net` at `input_size` — measured from XLA's
    own cost analysis of the traced forward (reference hapi/dynamic_flops
    keeps a hand-maintained per-layer registry; the compiler's count
    covers every op, custom ones included, so `custom_ops` is accepted
    for API parity but unnecessary)."""
    import numpy as _np

    import jax as _j
    import jax.numpy as _jnp

    x = _jnp.zeros(tuple(input_size), _jnp.float32)

    def fwd(xv):
        out = net(Tensor(xv, _internal=True))
        return out._value if isinstance(out, Tensor) else out

    try:
        cost = _j.jit(fwd).lower(x).compile().cost_analysis()
    except Exception as e:
        import warnings as _w
        _w.warn(f"paddle.flops could not trace the forward at input_size="
                f"{tuple(input_size)} ({type(e).__name__}: {e}); "
                f"returning 0")
        return 0
    total = int(cost.get("flops", 0.0)) if cost else 0
    if print_detail:
        per_param = sum(int(_np.prod(p.shape)) for p in net.parameters())
        print(f"Total Flops: {total}  Total Params: {per_param}")
    return total
