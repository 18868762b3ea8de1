"""hetu_tpu — a TPU-native distributed deep-learning framework.

Capability parity with AFDWang/Hetu (define-then-run dataflow graphs,
DP/TP/PP/EP(+SP/CP) parallelism, PS-backed sparse embeddings with bounded
staleness caches, auto-parallel search), rebuilt idiomatically on
JAX/XLA/Pallas: the op DAG traces into a single jitted XLA program,
collectives come from GSPMD/shard_map over a device mesh, and the hot kernels
are Pallas.  See SURVEY.md for the reference structural map this follows.
"""

from __future__ import annotations

import time as _time
_import_began = _time.perf_counter()   # telemetry's hetu_import_seconds

import numpy as np

from .graph import (Op, PlaceholderOp, VariableOp, find_topo_sort,
                    graph_variables, gradients, Executor, stage,
                    name_scope, remat, scope, scopes)
from . import initializers as init
from .ops import *  # noqa: F401,F403
from .optim import (SGDOptimizer, MomentumOptimizer, AdaGradOptimizer,
                    AdamOptimizer, AdamWOptimizer, AMSGradOptimizer,
                    LambOptimizer)
from .optim import lr_scheduler
from . import ps
from . import resilience
from .resilience import (CheckpointError, GuardTripped,
                         RollingCheckpointManager, StepGuard, retry)
from . import metrics
from . import telemetry
from .dataloader import (Dataloader, DataloaderOp, dataloader_op,
                         block_diffusion_noise)
from .datasets.prefetch import DevicePrefetcher, prefetch_feeds
from .logger import HetuLogger, WandbLogger
from .profiler import HetuProfiler, HetuSimulator
from . import timeline
from . import embed_compress
from . import onnx
from . import graphboard
from .launcher import DistConfig, launch, launch_local, initialize_from_env

__version__ = "0.1.0"

#: seconds this package's import took (jax's own import included where
#: this was the first to ask for it): ``hetu_import_seconds`` once
#: ``telemetry.enable()`` runs
import_seconds = _time.perf_counter() - _import_began


def placeholder_op(name, shape=None, dtype=np.float32, trainable=False):
    """Create a fed input node (reference: gpu_ops/Variable.py)."""
    return PlaceholderOp(name, shape=shape, dtype=dtype)


def Variable(name, value=None, initializer=None, shape=None, trainable=True,
             dtype=np.float32):
    """Create a persistent (optionally trainable) tensor.

    Either ``value`` (a concrete numpy array) or ``initializer`` + ``shape``
    must be given, matching the reference's Variable signature.
    """
    if value is not None:
        value = np.asarray(value)
        initializer = init.NumpyInit(value)
        shape = value.shape
    assert initializer is not None and shape is not None, \
        "Variable needs value= or (initializer=, shape=)"
    return VariableOp(name, shape, initializer, trainable=trainable,
                      dtype=dtype)


# torch/tf-style aliases used across reference examples
scalar = lambda name, value: Variable(name, value=np.asarray(value))  # noqa: E731
