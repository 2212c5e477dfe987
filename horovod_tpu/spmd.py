"""SPMD entry points: run per-device train steps over the communicator mesh.

This is the TPU-native replacement for the reference's process model (one
Python process per GPU, upstream ``horovod/runner``): instead of N processes
each executing the script, one controller traces the step function once and
``shard_map`` runs it on every device, with ``horovod_tpu`` collectives
lowering to XLA ops inside.
"""

from __future__ import annotations

import functools
import weakref
from typing import Any, Callable, Optional

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from horovod_tpu import core
from horovod_tpu import tracing as _tracing

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
    # The function's name labels its sync manifest, its set-up ledger
    # series and its scope table (tracing.py). ``traced`` runs while jit
    # traces and never per step: the returned object is still the bare
    # ``jax.jit``, and the program keeps the function's name
    # (``jit_<name>``).
    name = getattr(fn, "__name__", "spmd_fn")
    _tracing.note_program(name)
    jitted = []                 # a weak reference: no cycle through fn

    @functools.wraps(fn)
    def traced(*args, **kwargs):
        if not kwargs and not static_argnums:
            _tracing.note_lowering(name, jitted[0],
                                   _global_shapes(args, in_specs, m))
        with _tracing.program(name):
            return fn(*args, **kwargs)

    mapped = jax.shard_map(traced, mesh=m, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)
    step = jax.jit(mapped, donate_argnums=donate_argnums,
                   static_argnums=static_argnums)
    jitted.append(weakref.ref(step))
    return step


def _global_shapes(args, in_specs, mesh):
    """The arguments a mapped function is traced with, seen from outside
    the map: each leaf's per-device shape times the mesh axes its spec
    splits it over, with the ``NamedSharding`` that spec and the mesh fix,
    as ``jax.ShapeDtypeStruct``s (what ``.lower`` takes in place of
    arrays). ``in_specs`` is a prefix of ``args``, as shard_map reads it."""
    def leaf(spec, x):
        shape = list(x.shape)
        for dim, axes in enumerate(spec):
            for axis in ((axes,) if isinstance(axes, str) else axes or ()):
                shape[dim] *= mesh.shape[axis]
        return jax.ShapeDtypeStruct(tuple(shape), x.dtype,
                                    sharding=NamedSharding(mesh, spec),
                                    weak_type=getattr(x, "weak_type", False))

    return jax.tree_util.tree_map(
        lambda spec, sub: jax.tree_util.tree_map(
            lambda x: leaf(spec, x), sub),
        in_specs, args, is_leaf=lambda s: isinstance(s, P))


def spmd_data_sharding() -> NamedSharding:
    """NamedSharding that splits axis 0 of a host batch across the
    communicator (the data-parallel input layout)."""
    return NamedSharding(core.mesh(), P(core.axis_name()))
