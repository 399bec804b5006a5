"""mxnet_tpu: a TPU-native deep-learning framework with MXNet's capabilities.

Brand-new implementation (not a port): the compute path is JAX/XLA/Pallas,
scheduling and memory are XLA's, and distribution is ``jax.sharding`` over
device meshes. See SURVEY.md for the capability map against the reference
(Apache MXNet ~1.2, rahul003 fork).

Usage mirrors MXNet::

    import mxnet_tpu as mx
    x = mx.nd.ones((2, 3), ctx=mx.tpu(0))
    net = mx.sym.FullyConnected(mx.sym.Variable('data'), num_hidden=10)
"""
import time as _time
_T_IMPORT = _time.perf_counter()    # setup_seconds{phase="import"}

__version__ = "0.1.0"


def _maybe_init_distributed():
    """When spawned by tools/launch.py (reference DMLC env) or
    tools/run_multihost.py (MXTPU_NUM_PROCESSES env, the kvstore='tpu'
    contract — see kvstore_tpu/dist.py), join the collective world
    BEFORE anything touches the XLA backend (jax.distributed.initialize
    must run first). The reference does the analogous bootstrap on
    import: a DMLC_ROLE=server process enters the ps-lite server loop
    from python/mxnet/kvstore_server.py.

    DELIBERATE duplication of kvstore_tpu/dist.initialize_from_env:
    this must run before ANY heavy import (importing kvstore_tpu pulls
    jax.numpy/ndarray, touching the XLA backend we must precede), so
    the env contract is restated here — keep the two in sync."""
    import os
    is_worker = os.environ.get("DMLC_ROLE") == "worker"
    n_tpu = int(os.environ.get("MXTPU_NUM_PROCESSES", "0") or 0)
    if not is_worker and n_tpu <= 1:
        return
    n = n_tpu if n_tpu > 1 else int(os.environ.get("DMLC_NUM_WORKER", "1"))
    if n <= 1:
        return
    uri = os.environ.get("MXTPU_COORDINATOR")
    if uri is None:
        root = os.environ.get("DMLC_PS_ROOT_URI")
        port = os.environ.get("DMLC_PS_ROOT_PORT")
        uri = "%s:%s" % (root, port) if root and port else None
    if uri is None:
        # same contract as dist.initialize_from_env: a promised world
        # with no coordinator must fail HERE, before the XLA backend is
        # live, not later at kvstore creation with a weaker message
        raise ImportError(
            "distributed worker env found (num processes %d) but no "
            "coordinator address (MXTPU_COORDINATOR=host:port, or "
            "DMLC_PS_ROOT_URI/_PORT). Launch workers via "
            "tools/run_multihost.py or tools/launch.py, which set the "
            "whole contract." % n)
    rank = os.environ.get("MXTPU_PROCESS_ID")
    if rank is None:
        rank = os.environ.get("MXTPU_WORKER_RANK")
    if rank is None:
        raise ImportError(
            "distributed worker env found (num processes %d) but no rank "
            "(MXTPU_PROCESS_ID / MXTPU_WORKER_RANK). Launch workers via "
            "tools/run_multihost.py or tools/launch.py — a collective "
            "world needs ranks pinned at spawn (ps-lite assigned them "
            "dynamically)." % n)
    import jax
    jax.distributed.initialize(uri, num_processes=n, process_id=int(rank))
    # keep this process' eager/jit results on its own devices: without a
    # default device, multi-controller jit replicates outputs across the
    # whole world and host reads (asnumpy) of them fail
    jax.config.update("jax_default_device", jax.local_devices()[0])


_maybe_init_distributed()

from .base import MXNetError
from .context import (Context, cpu, gpu, tpu, cpu_pinned, current_context,
                      num_gpus, num_tpus)
from . import random
from . import name
from . import autograd
from . import ndarray
from . import ndarray as nd
from .ndarray import NDArray
from . import symbol
from . import symbol as sym
from .symbol import Symbol, AttrScope
from . import executor
from .executor import Executor
from . import initializer
from . import initializer as init
from . import optimizer
from . import lr_scheduler
from . import metric
from . import callback
from . import io
from .io import DataBatch, DataIter
from . import kvstore
from . import kvstore as kv
from .kvstore import KVStore
from . import model
from . import module
from . import module as mod
from .module import Module
from . import parallel
from . import sharding
from . import models
from . import gluon
from . import recordio
from . import image
from . import operator
from . import visualization
from . import viz
from . import contrib
from . import rnn
from . import rtc
from . import config
from . import predictor
from . import serving
from . import decode
from . import fleet
from . import profiler
from . import telemetry
from . import pallas
from . import aot
from . import checkpoint
from . import embedding
from . import kvstore_tpu
from . import monitor
from .monitor import Monitor
from . import test_utils

# this file's own first line to here: what a process pays for the
# package before it can bind anything (jax's import too, where the
# process had not imported jax yet)
telemetry.tracing.SETUP_SECONDS.labels(phase="import").inc(
    _time.perf_counter() - _T_IMPORT)

# server/scheduler-role processes enter their loop here, at the END of
# the package import (reference wires kvstore_server the same way,
# python/mxnet/__init__.py:57). It must NOT run mid-import: the serve
# loop would hold the package's import lock forever and any handler
# thread importing a submodule (optimizer, compression) would deadlock.
from . import kvstore_server  # noqa: E402,F401
kvstore_server._init_kvstore_server_module()
