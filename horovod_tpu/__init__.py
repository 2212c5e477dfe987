"""horovod_tpu: a TPU-native distributed training framework with Horovod's
capabilities (reference: DelphianCalamity/horovod), rebuilt on jax/XLA.

    import horovod_tpu as hvd
    hvd.init()
    step = hvd.spmd(train_step)   # shard_map over the communicator mesh
    ...

See SURVEY.md for the component inventory mapping every public symbol to its
upstream equivalent.
"""

import time as _time

_IMPORT_T0 = _time.perf_counter()   # -> the import_seconds gauge, below

from horovod_tpu.core import (  # noqa: F401,E402
    init, shutdown, is_initialized, rank, size, local_rank, local_size,
    cross_rank, cross_size, mesh, axis_name, build_info, in_spmd_context,
    topology, topology_str,
    mesh2d, mesh_spec, dp_size, mp_size, dp_rank, mp_rank,
)
# dp×mp multi-axis sharding: model-parallel partition rules, ZeRO-2/3
# training helpers, and tensor-parallel serving splits on the named 2-d
# mesh (hvd.parallel.mp — docs/PARALLELISM.md).
from horovod_tpu import parallel  # noqa: F401
from horovod_tpu.collective import (  # noqa: F401
    ReduceOp, Average, Sum, Min, Max, Product, Adasum,
    allreduce, allreduce_, allreduce_async, grouped_allreduce,
    grouped_allgather, grouped_reducescatter,
    allgather, ragged_allgather, broadcast, broadcast_, alltoall,
    reducescatter,
    barrier, synchronize, poll, join, broadcast_object, allgather_object,
)
from horovod_tpu.compression import Compression  # noqa: F401
# ``hvd.metrics`` is the (callable) metrics submodule: ``hvd.metrics()``
# returns the snapshot dict, and the full subsystem lives on it —
# ``hvd.metrics.to_prometheus()``, ``hvd.metrics.start_stall_watchdog()``,
# ``hvd.metrics.start_metrics_flusher()``, ...
from horovod_tpu import metrics  # noqa: F401
# Overlapped gradient sync: algorithm selection (auto|psum|rs_ag|
# chunked_rs_ag), chunked RS+AG pipelines, backward taps
# (docs/PERFORMANCE.md).
from horovod_tpu import overlap  # noqa: F401
# Continuous-batching inference: hvd.serving.InferenceEngine (paged KV
# cache, request scheduler, multi-replica dispatch — docs/SERVING.md).
from horovod_tpu import serving  # noqa: F401
# Always-on roofline introspection: program registry (MFU/HFU/peak-HBM
# gauges), recompile detection with argument blame, memory accounting,
# triggered jax.profiler captures, and hvd.doctor() automated diagnosis
# (docs/OBSERVABILITY.md "Roofline gauges" / "Doctor").
from horovod_tpu import profiler  # noqa: F401
from horovod_tpu.profiler import doctor, profile  # noqa: F401
from horovod_tpu.metrics import metrics_http, reset_metrics  # noqa: F401
# Fleet health plane (docs/OBSERVABILITY.md "Fleet health plane"):
# windowed time-series over registry snapshots (hvd.timeseries), the
# continuous doctor with fire/clear hysteresis + SLO burn-rate alerts
# and the per-replica scrape collector (hvd.health), and the hvd.top()
# terminal dashboard (CLI: tools/fleet_top.py).
from horovod_tpu import timeseries  # noqa: F401
from horovod_tpu import health  # noqa: F401
from horovod_tpu.health import top  # noqa: F401
# Observable runtime config (docs/OBSERVABILITY.md "Config plane"): the
# fleet-wide knob mutation bus — typed mutable-knob registry over
# config.py, hvd.set_config() with a JSONL audit ledger + config_epoch,
# measured-effect experiment windows with revert-on-regression, and the
# auth-gated set_config RPC / POST /config surfaces.
from horovod_tpu import confbus  # noqa: F401
from horovod_tpu.confbus import set_config  # noqa: F401
# Flight recorder & postmortem plane (docs/OBSERVABILITY.md "Postmortem
# bundles"): an always-on black box of bounded rings (HOROVOD_BLACKBOX),
# crash-time forensic bundles (hvd.dump_postmortem), and the offline
# root-cause analyzer (hvd.postmortem_report; CLI: tools/postmortem.py).
from horovod_tpu import blackbox  # noqa: F401
from horovod_tpu.blackbox import (  # noqa: F401
    dump_postmortem, postmortem_report,
)
from horovod_tpu.optimizer import (  # noqa: F401
    AutotunedStep, DistributedOptimizer, DistributedGradientTape,
    ErrorFeedbackState, accumulation_has_updated, reset_error_feedback,
    grad, value_and_grad, allreduce_gradients, broadcast_parameters,
    broadcast_optimizer_state, broadcast_variables,
)
from horovod_tpu.optimizer_sharded import (  # noqa: F401
    ShardedAdamWState, sharded_adamw,
)
# Preemption tolerance (docs/ELASTIC.md): commit/restore elastic states
# (hvd.elastic), async sharded checkpoints with two-phase-commit manifests
# (hvd.checkpoint_sharded), instrumented full-state orbax checkpoints
# (hvd.checkpoint), and the fault-injection harness (hvd.faults,
# HOROVOD_FAULT_PLAN).
from horovod_tpu import checkpoint  # noqa: F401
from horovod_tpu import checkpoint_sharded  # noqa: F401
from horovod_tpu import elastic  # noqa: F401
from horovod_tpu import faults  # noqa: F401
from horovod_tpu.checkpoint_sharded import (  # noqa: F401
    ShardedCheckpointManager,
)
from horovod_tpu.process_set import (  # noqa: F401
    ProcessSet, add_process_set, remove_process_set, global_process_set,
)
from horovod_tpu.spmd import spmd, spmd_data_sharding  # noqa: F401
from horovod_tpu.timeline import (  # noqa: F401
    start_timeline, stop_timeline, merge_timelines,
)

__version__ = "0.1.0"

# The package's own share of a process's set-up (tracing.NAMES); with
# core.init's init_seconds it is what the program adds before any jit.
metrics.gauge("import_seconds").set(_time.perf_counter() - _IMPORT_T0)


def mpi_threads_supported() -> bool:
    """Parity shim: no MPI on TPU (upstream ``hvd.mpi_threads_supported``)."""
    return False


def mpi_enabled() -> bool:
    return False


def gloo_enabled() -> bool:
    return False


def nccl_built() -> bool:
    return False


def mpi_built() -> bool:
    return False


def gloo_built() -> bool:
    return False


def cuda_built() -> bool:
    return False


def rocm_built() -> bool:
    return False
