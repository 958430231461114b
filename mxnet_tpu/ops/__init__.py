"""Operator library.

TPU-native re-emission of the reference's ``src/operator`` tree: every op is a
pure JAX function (XLA HLO), with Pallas kernels for the few fusions XLA cannot
express well.  Gradients come from JAX VJP — the FGradient registry of the
reference (ref: 3rdparty/tvm/nnvm — NNVM_REGISTER_OP / FGradient) is subsumed
by jax.vjp, which is strictly more general.
"""
from . import registry  # noqa: F401
from .registry import OPS, register_op, get_op, alias_op  # noqa: F401

# Import op families for registration side-effects.
from . import elementwise  # noqa: F401
from . import reduce as reduce_ops  # noqa: F401
from . import matrix  # noqa: F401
from . import nn  # noqa: F401
from . import optimizer_ops  # noqa: F401
from . import sequence  # noqa: F401
from . import loss  # noqa: F401
from . import rnn  # noqa: F401
from . import attention  # noqa: F401
from . import state_space  # noqa: F401
from . import rotary  # noqa: F401
from . import paged_attention  # noqa: F401
from . import image  # noqa: F401
from . import multibox  # noqa: F401
from . import quantization  # noqa: F401
from . import control_flow  # noqa: F401
from . import random_ops  # noqa: F401
