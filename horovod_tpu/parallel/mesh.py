"""Mesh construction over TPU slices.

Replaces the reference's topology discovery (``horovod/runner/driver`` host
slots + ``horovod/common/topology``-style rank maps): ``make_mesh`` builds an
ICI-aware ``jax.sharding.Mesh`` whose named axes carry the parallelism
strategy. Axis order matters on hardware: later axes map to faster (ICI)
topology dimensions, so put data-parallel first (it tolerates DCN) and
tensor/sequence parallel last (they need ICI bandwidth).
"""

from __future__ import annotations

import logging
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import Mesh

log = logging.getLogger("horovod_tpu")

__all__ = ["make_mesh", "parse_topology", "detect_topology",
           "torus_groups", "parse_mesh", "format_mesh", "validate_mesh",
           "make_mesh2d"]


def make_mesh(axes: Dict[str, int], devices: Optional[Sequence] = None,
              allow_split_physical_axes: bool = True) -> Mesh:
    """Build a named mesh, e.g. ``make_mesh({"dp": 4, "tp": 2})``.

    An axis size of ``-1`` is inferred from the device count (at most one).
    On TPU, ``mesh_utils.create_device_mesh`` aligns logical axes with the
    physical torus so contiguous axes ride ICI links.
    """
    devs = list(devices if devices is not None else jax.devices())
    names = tuple(axes.keys())
    sizes = list(axes.values())
    if sizes.count(-1) > 1:
        raise ValueError("at most one axis may be -1")
    known = int(np.prod([s for s in sizes if s != -1]))
    if -1 in sizes:
        if len(devs) % known:
            raise ValueError(
                f"cannot infer axis: {len(devs)} devices not divisible by {known}")
        sizes[sizes.index(-1)] = len(devs) // known
    total = int(np.prod(sizes))
    if total != len(devs):
        raise ValueError(
            f"mesh {dict(zip(names, sizes))} needs {total} devices, "
            f"have {len(devs)}")
    if devices is None and jax.default_backend() == "tpu":
        # A failure here raises: a naive reshape would still run, with
        # the logical axes laid across the torus at random.
        arr = mesh_utils.create_device_mesh(
            tuple(sizes),
            allow_split_physical_axes=allow_split_physical_axes)
        return Mesh(arr, names)
    arr = np.asarray(devs, dtype=object).reshape(tuple(sizes))
    return Mesh(arr, names)


# ---------------------------------------------------------------------------
# dp x mp mesh specs (the HOROVOD_MESH axis)
# ---------------------------------------------------------------------------

def parse_mesh(spec: str) -> Tuple[int, int]:
    """Parse a ``HOROVOD_MESH`` spec like ``"dp2xmp4"`` into ``(dp, mp)``.

    Grammar is fixed to the two named axes — data-parallel first (DCN
    tolerant), model-parallel last (ICI hungry) — so the string also
    documents the placement contract.
    """
    import re
    m = re.fullmatch(r"dp(\d+)xmp(\d+)", str(spec).strip().lower())
    if not m:
        raise ValueError(
            f"invalid HOROVOD_MESH {spec!r}; expected 'dpXxmpY' like "
            f"'dp2xmp4' (data-parallel degree X, model-parallel degree Y)")
    dp, mp = int(m.group(1)), int(m.group(2))
    if dp < 1 or mp < 1:
        raise ValueError(
            f"invalid HOROVOD_MESH {spec!r}: both degrees must be >= 1")
    return dp, mp


def format_mesh(dp: int, mp: int) -> str:
    """``(dp, mp)`` -> the canonical ``"dpXxmpY"`` spec string."""
    return f"dp{int(dp)}xmp{int(mp)}"


def validate_mesh(dp: int, mp: int, world: int,
                  topology: Optional[Sequence[int]] = None
                  ) -> Tuple[int, int]:
    """Check a dp x mp request against the world size and the detected
    torus. ``dp * mp`` must equal ``world`` exactly, and when the fabric
    has real topology dims the mp degree must nest with the innermost
    (fastest-wraparound) dim — either filling whole inner rings
    (``mp % inner == 0``) or subdividing one (``inner % mp == 0``) — so
    the tensor-parallel collectives stay on contiguous ICI links.
    """
    if dp * mp != world:
        raise ValueError(
            f"HOROVOD_MESH {format_mesh(dp, mp)} needs {dp * mp} devices "
            f"but the world has {world}; the mesh must factor the world "
            f"exactly")
    dims = tuple(int(d) for d in (topology or ()))
    if mp > 1 and len(dims) > 1:
        inner = dims[-1]
        if mp % inner != 0 and inner % mp != 0:
            raise ValueError(
                f"HOROVOD_MESH {format_mesh(dp, mp)}: mp={mp} does not "
                f"nest with the detected topology {'x'.join(map(str, dims))} "
                f"(innermost dim {inner}); pick mp dividing {inner} or a "
                f"multiple of it so tp collectives stay on ICI")
    return dp, mp


def make_mesh2d(dp: int, mp: int,
                devices: Optional[Sequence] = None) -> Mesh:
    """Build the 2-D ``("dp", "mp")`` mesh for a validated dp x mp spec.

    Device order is row-major over the flat communicator order: global
    rank ``r`` sits at ``(dp=r // mp, mp=r % mp)``, so each mp group is a
    contiguous run of ranks — on TPU the same contiguity that
    :func:`validate_mesh` checked rides the innermost torus dim.
    """
    return make_mesh({"dp": int(dp), "mp": int(mp)}, devices)


# ---------------------------------------------------------------------------
# torus topology discovery (the `algorithm=` topology axis)
# ---------------------------------------------------------------------------

def parse_topology(spec: str) -> Tuple[int, ...]:
    """Parse a ``HOROVOD_TOPOLOGY`` spec like ``"2x2"`` or ``"4x8x2"``
    into a dims tuple. Every dim must be a positive integer."""
    parts = str(spec).strip().lower().split("x")
    try:
        dims = tuple(int(p) for p in parts)
    except ValueError:
        dims = ()
    if not dims or any(d < 1 for d in dims):
        raise ValueError(
            f"invalid HOROVOD_TOPOLOGY {spec!r}; expected positive torus "
            f"dims like '2x2' or '4x8'")
    return dims


def detect_topology(world: int, devices: Optional[Sequence] = None,
                    override: Optional[str] = None) -> Tuple[int, ...]:
    """Torus/mesh dims of the slice backing a ``world``-device axis.

    Resolution order: an explicit ``override`` spec (``HOROVOD_TOPOLOGY``,
    e.g. ``"2x2"`` — its product must equal ``world``); else, on TPU, the
    coordinate spans of ``jax.devices()`` (dims of extent 1 dropped, a
    trailing cores-per-chip dim appended when chips are multi-core); else
    a flat 1-D ring ``(world,)``. Detection never raises on unexpected
    device metadata — anything that does not factor ``world`` cleanly
    falls back to 1-D, which keeps every pre-topology lowering valid.
    """
    if override:
        dims = parse_topology(override)
        if int(np.prod(dims)) != world:
            raise ValueError(
                f"HOROVOD_TOPOLOGY {override!r} describes "
                f"{int(np.prod(dims))} devices but the world has {world}")
        return dims
    if world <= 1:
        return (max(world, 1),)
    devs = list(devices if devices is not None else jax.devices())
    try:
        coords = [tuple(d.coords) for d in devs]
    except Exception:
        return (world,)
    try:
        spans = [len({c[i] for c in coords}) for i in range(len(coords[0]))]
        cores = len({getattr(d, "core_on_chip", 0) for d in devs})
        dims = tuple(s for s in spans if s > 1)
        if cores > 1:
            dims = dims + (cores,)
        if dims and int(np.prod(dims)) == world:
            return dims
    except Exception:
        pass
    log.debug("device coords do not factor a %d-device torus; "
              "treating the slice as a 1-D ring", world)
    return (world,)


def torus_groups(dims: Sequence[int]) -> List[List[List[int]]]:
    """Per-dim ``axis_index_groups`` for sub-axis collectives on a flat
    rank axis laid out row-major over ``dims``.

    Entry ``j`` partitions the ranks into lines along torus dim ``j``
    (all other coords fixed, dim-``j`` coordinate increasing) — a full
    equal-size partition of the axis, which is exactly what
    ``axis_index_groups`` supports under shard_map.
    """
    dims = tuple(int(d) for d in dims)
    ranks = np.arange(int(np.prod(dims))).reshape(dims)
    out = []
    for j in range(len(dims)):
        moved = np.moveaxis(ranks, j, -1).reshape(-1, dims[j])
        out.append([[int(r) for r in row] for row in moved])
    return out
