"""SPMD entry points: run per-device train steps over the communicator mesh.

This is the TPU-native replacement for the reference's process model (one
Python process per GPU, upstream ``horovod/runner``): instead of N processes
each executing the script, one controller traces the step function once and
``shard_map`` runs it on every device, with ``horovod_tpu`` collectives
lowering to XLA ops inside.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from horovod_tpu import core

__all__ = ["spmd", "spmd_data_sharding"]


def spmd(fn: Callable, *, in_specs: Any = None, out_specs: Any = None,
         donate_argnums=(), static_argnums=()) -> Callable:
    """Wrap a per-device step function for SPMD execution over the global
    communicator mesh and jit it.

    Defaults mirror Horovod's model: every argument is replicated
    (``P()``) except that callers typically shard the batch — pass
    ``in_specs`` to override per-argument. Inside ``fn``, ``hvd.rank()``,
    ``hvd.allreduce`` etc. resolve against the mesh axis.
    """
    m = core.mesh()
    axis = core.axis_name()
    if in_specs is None:
        in_specs = P()
    if out_specs is None:
        out_specs = P()
    mapped = jax.shard_map(fn, mesh=m, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)
    return jax.jit(mapped, donate_argnums=donate_argnums,
                   static_argnums=static_argnums)


def spmd_data_sharding() -> NamedSharding:
    """NamedSharding that splits axis 0 of a host batch across the
    communicator (the data-parallel input layout)."""
    return NamedSharding(core.mesh(), P(core.axis_name()))
