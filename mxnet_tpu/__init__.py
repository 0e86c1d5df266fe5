"""mxnet_tpu — a TPU-native deep learning framework with the capabilities of
Apache MXNet 0.10 (NNVM era), re-designed for jax/XLA/Pallas.

Import as ``import mxnet_tpu as mx``; the namespace mirrors the reference's
``python/mxnet`` package: ``mx.nd``, ``mx.sym``, ``mx.mod``, ``mx.io``,
``mx.kv``, ``mx.metric``, ``mx.optimizer``, ``mx.init``, ``mx.rnn``, etc.
"""

from . import telemetry

# top to bottom of this file, jax's own import included: what a new process
# pays before its first line of user code (closed at the end of the file)
_import_span = telemetry.span("startup.import")
_import_span.__enter__()


def _maybe_init_distributed():
    """Join the multi-host jax runtime when launched by tools/launch.py.

    Must run before anything initialises the XLA backend, so it lives at
    package import — the analogue of the reference auto-entering the server
    loop on import when DMLC_ROLE=server (python/mxnet/kvstore_server.py:58).
    """
    from . import env  # stdlib-only; safe before jax

    coord = env.get("MXNET_COORDINATOR")
    nproc = env.get("MXNET_NUM_PROCS")
    # raw(): rank 0 unset vs rank 0 exported are different cases — only a
    # launcher-exported rank means this process belongs to a multi-host job
    if (env.get("MXNET_KV_TRANSPORT") or "mesh").lower() == "tcp":
        # elastic plane: membership is dynamic, but the jax runtime pins
        # world size at initialize — every process stays a single-host jax
        # world and the kvstore's TCP transport carries all collectives
        return
    if coord and nproc > 1 and env.raw("MXNET_PROC_ID") is not None:
        import jax

        try:
            # (jax.process_count() would itself initialise the backend, so
            # no pre-check — this is the first jax call in the process)
            jax.distributed.initialize(
                coordinator_address=coord,
                num_processes=nproc,
                process_id=env.get("MXNET_PROC_ID"),
            )
        except RuntimeError:
            # the worker script (or another framework) already initialised
            # the distributed runtime — fine, DistKVStore validates the
            # process count when created
            pass


def _place_compile_cache():
    """Point jax's persistent compilation cache at a fixed directory.

    ``JAX_COMPILATION_CACHE_DIR`` set: jax reads it itself and nothing is
    set here — that is how a deployment (or a machine that keeps one
    directory between runs) moves the cache. Unset: the cache lives at
    ``<checkout>/.jax_cache``, derived from this package's own location
    and nothing else — the directory is part of jax's cache key, so a
    path that moves between runs never hits. Every ``lower().compile()``
    (``aot.AOTProgram``) goes through this cache. Must run before the
    first compile, so it lives at package import.
    """
    import os

    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    jax.config.update("jax_compilation_cache_dir",
                      os.path.join(root, ".jax_cache"))


_maybe_init_distributed()
_place_compile_cache()

from .base import MXNetError, __version__
from . import env  # noqa: F401 (also imported inside _maybe_init_distributed)
from .context import Context, cpu, gpu, tpu, cpu_pinned, current_context, num_gpus

from . import ndarray
from . import ndarray as nd
from . import sparse_ndarray
from . import sparse_ndarray as sparse_nd
from .sparse_ndarray import RowSparseNDArray, CSRNDArray
from . import random
from . import random as rnd
from . import autograd

from .ndarray import NDArray

# populated by later build stages; import lazily where heavy
from . import symbol
from . import symbol as sym
from .symbol import Symbol, Variable
from . import executor
from .executor import Executor
from . import attribute
from .attribute import AttrScope
from . import engine
from . import name
from .name import NameManager

from . import initializer
from . import initializer as init
from . import optimizer
from . import optimizer as opt
from .optimizer import Optimizer
from . import lr_scheduler
from . import metric
from . import io
from . import recordio
from . import kvstore
from . import kvstore as kv
from . import callback
from . import monitor
from . import model
from . import checkpoint
from .checkpoint import CheckpointConfig
from . import faultinject
from .model import FeedForward
from . import module
from . import module as mod
from . import rnn
from . import image
from . import profiler
from . import aot
from . import visualization
from . import visualization as viz
from . import test_utils
from . import contrib
from . import parallel
from . import operator
from . import predictor
from . import serving
from . import rtc

_import_span.__exit__(None, None, None)
del _import_span
