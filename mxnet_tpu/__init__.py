"""mxnet_tpu: a TPU-native deep-learning framework with the capability
surface of Apache MXNet v0.11 (reference at /root/reference), built on
JAX/XLA/Pallas/pjit instead of mshadow/CUDA/NNVM/ps-lite.

Typical use mirrors the reference:

    import mxnet_tpu as mx
    x = mx.nd.zeros((2, 3), ctx=mx.tpu(0))
    net = mx.sym.FullyConnected(mx.sym.Variable('data'), num_hidden=10)
"""
import time as _time

_import_start_ns = _time.perf_counter_ns()

from . import base  # noqa: F401
from . import ops  # noqa: F401  (populates the op table)
from . import ndarray  # noqa: F401
from . import ndarray as nd  # noqa: F401
from . import autograd  # noqa: F401
from . import random  # noqa: F401
from . import random as rnd  # noqa: F401
from . import symbol  # noqa: F401
from . import symbol as sym  # noqa: F401
from . import executor  # noqa: F401
from . import executor_manager  # noqa: F401
from .executor import Executor  # noqa: F401
from . import name  # noqa: F401
from . import attribute  # noqa: F401
from . import registry  # noqa: F401
from . import libinfo  # noqa: F401
from . import log  # noqa: F401
from . import misc  # noqa: F401
from .symbol import AttrScope, Symbol  # noqa: F401
from . import initializer  # noqa: F401
from . import initializer as init  # noqa: F401
from . import io  # noqa: F401
from . import recordio  # noqa: F401
from . import image  # noqa: F401
from . import image as img  # noqa: F401
from . import image_det  # noqa: F401
for _n in image_det.__all__:  # reference exposes det under mx.image.*
    setattr(image, _n, getattr(image_det, _n))
del _n
from . import kvstore  # noqa: F401
from . import kvstore as kv  # noqa: F401
from . import kvstore_server  # noqa: F401
from . import ndarray_doc  # noqa: F401
from . import symbol_doc  # noqa: F401
from . import lr_scheduler  # noqa: F401
from . import metric  # noqa: F401
from . import model  # noqa: F401
from . import module  # noqa: F401
from . import module as mod  # noqa: F401
from . import callback  # noqa: F401
from . import gluon  # noqa: F401
from . import rnn  # noqa: F401
from . import config  # noqa: F401
from . import monitor  # noqa: F401
from . import monitor as mon  # noqa: F401
from . import operator  # noqa: F401
from . import optimizer  # noqa: F401
from . import profiler  # noqa: F401
from . import rtc  # noqa: F401
from . import torch as th  # noqa: F401
from . import test_utils  # noqa: F401
from . import contrib  # noqa: F401
from . import parallel  # noqa: F401
from . import perf  # noqa: F401
from . import compiler  # noqa: F401
compiler.cache.configure_jax_cache()
from . import resilience  # noqa: F401
from . import serving  # noqa: F401
from . import quant  # noqa: F401
from . import notebook  # noqa: F401
from . import visualization  # noqa: F401
from . import visualization as viz  # noqa: F401
from .monitor import Monitor  # noqa: F401
from .io import DataBatch, DataIter  # noqa: F401
from .base import MXNetError  # noqa: F401
from .context import Context, cpu, current_context, gpu, num_gpus, num_tpus, tpu  # noqa: F401
from .ndarray import NDArray  # noqa: F401

__version__ = libinfo.__version__

# the import as one span of the program's own profiler: known only now
profiler.record("import.mxnet_tpu", _import_start_ns,
                _time.perf_counter_ns())
del _time, _import_start_ns
