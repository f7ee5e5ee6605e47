"""multiverso_tpu — a TPU-native parameter-server training framework.

Brand-new JAX/XLA/pjit implementation of the capabilities of Microsoft
Multiverso (the DMTK parameter server): sharded model tables in TPU HBM,
worker Get/Add push-pull in sync (BSP) and async (ASGD) modes, pluggable
jitted server-side updaters, allreduce model-average mode, checkpoint/resume,
flags, dashboards, and the reference applications (word2vec, logistic
regression). See SURVEY.md for the structural map of the reference this
framework re-implements TPU-first.
"""

import sys as _sys
import time as _time

# The start-up timeline's two stamps of this line (telemetry/startup.py):
# the clock, and whether the host application had brought the backend up.
_T_IMPORT0 = _time.time()
_bridge = _sys.modules.get("jax._src.xla_bridge")
_BACKEND_READY = _bridge is not None and _bridge.backends_are_initialized()

import os as _os  # noqa: E402

import jax as _jax  # noqa: E402


def _configure_jax() -> None:
    """Process-wide jax settings, applied once at import so CLIs, spawned
    ranks, ``bench.py`` and ``chip_smoke.py`` all share them.

    * Sharding-invariant PRNG: the legacy (non-partitionable) threefry
      lowering produces DIFFERENT random bits inside a GSPMD-partitioned
      program than in the single-device program (the in-graph window draws
      of the dp x tp word2vec block step diverged from the unsharded step,
      changing pair counts). Partitionable threefry computes each element
      from its global index, so draws are identical under any mesh layout —
      required for the "same keys -> same pairs" contract of
      build_sharded_block_step.
    * Persistent compilation cache: where ``JAX_COMPILATION_CACHE_DIR`` is
      set jax already honors it and nothing is set here; otherwise the
      cache lives at ``<checkout>/.jax_cache``. The path is part of the
      cache key, so it is fixed: no temp dir, pid or timestamp.
    """
    _jax.config.update("jax_threefry_partitionable", True)
    if not _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        checkout = _os.path.dirname(_os.path.dirname(
            _os.path.abspath(__file__)))
        _jax.config.update("jax_compilation_cache_dir",
                           _os.path.join(checkout, ".jax_cache"))


_configure_jax()

from multiverso_tpu.api import (aggregate, barrier, create_table,
                                create_table_group,
                                create_distributed_array_table,
                                create_distributed_kv_table,
                                create_distributed_matrix_table,
                                create_distributed_sparse_matrix_table,
                                finish_train, get_flag, init, net_bind,
                                net_connect,
                                is_master_worker, num_servers, num_workers,
                                rank, server_id, set_flag, shutdown, size,
                                worker_id)
from multiverso_tpu.core.options import (AddOption, ArrayTableOption,
                                         GetOption, KVTableOption,
                                         MatrixTableOption)

from multiverso_tpu.telemetry import startup as _startup

__version__ = "0.1.0"

__all__ = [
    "init", "shutdown", "barrier", "rank", "size", "num_workers",
    "num_servers", "worker_id", "server_id", "is_master_worker",
    "set_flag", "get_flag", "create_table", "create_table_group", "aggregate",
    "finish_train",
    "net_bind", "net_connect", "create_distributed_array_table",
    "create_distributed_matrix_table", "create_distributed_kv_table",
    "create_distributed_sparse_matrix_table",
    "AddOption", "GetOption", "ArrayTableOption", "MatrixTableOption",
    "KVTableOption",
]

_startup.imported(_T_IMPORT0, _BACKEND_READY)
