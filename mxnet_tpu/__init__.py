"""mxnet_tpu — a TPU-native deep learning framework with MXNet's capabilities.

A ground-up JAX/XLA/PJRT design (not a port) covering the reference stack
(ref: apache MXNet 1.x via the Jiaolong/mxnet fork — see SURVEY.md):
NDArray + autograd + Gluon + operator library + KVStore-semantics data
parallelism, with `mx.tpu()` as the headline context, hybridize() lowering to
single XLA computations, and mesh sharding (DP/TP/PP/SP/EP) replacing the
parameter server.

Usage mirrors the reference:

    import mxnet_tpu as mx
    from mxnet_tpu import nd, autograd, gluon
"""
import time as _time

_IMPORT_T0 = _time.perf_counter()   # telemetry.now_us's clock, in seconds

# Multi-process bring-up MUST precede any jax backend touch (jax.devices et
# al.), so when launched under the DMLC_* env contract (tools/launch.py) the
# coordination service connects before the rest of the package imports.
import os as _os

if int(_os.environ.get("DMLC_NUM_WORKER", "0") or 0) > 1:
    from . import distributed as _distributed

    _distributed.init()

from . import base
from . import config
from .base import MXNetError
from . import context
from .context import (Context, cpu, tpu, gpu, cpu_pinned,
                      current_context, num_tpus, num_gpus, gpu_memory_info)
from . import engine
from . import fault             # mx.fault — injection harness, retry, signals
from . import elastic           # mx.elastic — heartbeats, supervisor contract
from . import storage
from . import random
from . import autograd
from . import ndarray
from . import ndarray as nd
from .ndarray import NDArray
from . import initializer
from . import initializer as init
from . import lr_scheduler
from . import optimizer
from . import metric
from . import kvstore
from . import kvstore as kv
from . import distributed
from . import sparse
from . import recordio
from . import io
from . import amp
from . import callback
from . import operator
from . import contrib
from . import image
from . import util
from . import runtime
from . import test_utils
from . import visualization
from . import visualization as viz
ndarray.sparse = sparse      # mx.nd.sparse, matching the reference layout
from . import numpy as np           # mx.np — numpy-semantics frontend
from . import numpy_extension as npx  # mx.npx — set_np + neural ops
from . import profiler
from . import telemetry           # mx.telemetry — spans, metrics, compile events
from . import onnx
from . import parallel
from . import gluon
from . import symbol
from . import symbol as sym          # mx.sym — symbolic graph frontend
from . import executor
from . import module
from . import module as mod          # mx.mod — Module API
from . import serving                # mx.serving — inference serving runtime
from . import model                  # mx.model — checkpoint helpers
from . import rnn                    # mx.rnn — legacy symbolic RNN cells
from . import name                   # mx.name — NameManager/Prefix scopes
from . import monitor                # mx.monitor — layer-stat debugging
from . import monitor as mon
from . import attribute              # mx.attribute — AttrScope
from .attribute import AttrScope
from . import log                    # mx.log — logging helpers

config._apply_startup()

__version__ = "0.1.0"

waitall = engine.waitall

# when the program began, on ``time.perf_counter`` (the clock of every span):
# a process's own start to here is its runtime's, from here on the program's
telemetry.registry().gauge("process.import_t0_s").set(_IMPORT_T0)
telemetry.registry().gauge("process.import_ms").set(
    (_time.perf_counter() - _IMPORT_T0) * 1e3)
