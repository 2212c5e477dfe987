"""Core runtime state for horovod_tpu.

TPU-native rethink of Horovod's basics layer (upstream
``horovod/common/basics.py`` + ``horovod/common/operations.cc:horovod_init``).
Instead of spawning one process per accelerator and negotiating over MPI/Gloo,
``init()`` builds a :class:`jax.sharding.Mesh` over the TPU slice: the mesh
axis *is* the communicator, and XLA collectives over it ride the ICI fabric.

Two execution styles are supported, mirroring how the reference is used:

* **SPMD-under-jit** (the TPU-native path): user code runs inside
  ``shard_map`` over the global mesh; ``rank()`` is ``lax.axis_index`` and
  collectives lower to single XLA ops.
* **Multi-process** (one process per TPU host, like Horovod's one process per
  GPU): ``jax.distributed.initialize`` handles rendezvous; ``cross_rank`` /
  ``cross_size`` map to process index/count exactly like Horovod's
  cross-communicator (upstream ``horovod/common/basics.py:cross_rank``).
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

__all__ = [
    "init",
    "shutdown",
    "is_initialized",
    "rank",
    "size",
    "topology",
    "local_rank",
    "local_size",
    "cross_rank",
    "cross_size",
    "mesh",
    "mesh2d",
    "mesh_spec",
    "dp_size",
    "mp_size",
    "dp_rank",
    "mp_rank",
    "axis_name",
    "build_info",
    "init_epoch",
]

AXIS_NAME = "hvd"

# Monotone count of init() calls this process (elastic re-meshes bump it).
# Trace span phases carry it so a merged timeline can attribute collectives
# to communicator epochs; elastic membership changes appear as epoch
# boundaries in every rank's shard.
_INIT_EPOCH = 0


def init_epoch() -> int:
    """Communicator epoch: how many times ``init()`` has run (0 = never)."""
    return _INIT_EPOCH


@dataclasses.dataclass
class _Context:
    mesh: Mesh
    axis: str
    devices: tuple
    # Detected torus/mesh dims of the slice (parallel/mesh.py
    # detect_topology); (world,) when the fabric is a flat ring.
    topology: tuple = ()
    # The named 2-D ("dp", "mp") mesh over the SAME devices (HOROVOD_MESH;
    # dp=world x mp=1 when unset) and its (dp, mp) degrees. The 1-D
    # communicator mesh above stays the collective/process-set substrate;
    # the 2-D view is what parallel/mp.py shard_maps over.
    mesh2d: Optional[Mesh] = None
    mesh_dims: tuple = (1, 1)
    initialized: bool = True


_LOCK = threading.Lock()
_CTX: Optional[_Context] = None


class NotInitializedError(RuntimeError):
    def __init__(self):
        super().__init__(
            "horovod_tpu has not been initialized; call horovod_tpu.init() first."
        )


def _ctx() -> _Context:
    if _CTX is None:
        raise NotInitializedError()
    return _CTX


def init(devices: Optional[Sequence] = None, axis_name: str = AXIS_NAME,
         coordinator_address: Optional[str] = None,
         num_processes: Optional[int] = None,
         process_id: Optional[int] = None) -> None:
    """Initialize the global communicator.

    Mirrors ``hvd.init()`` (upstream ``horovod/common/basics.py:init``). On a
    multi-host TPU slice pass ``coordinator_address``/``num_processes``/
    ``process_id`` (or rely on TPU-VM metadata auto-detection inside
    ``jax.distributed.initialize``) to join the pod before the mesh is built.
    """
    global _CTX
    import os
    import time as _time
    t0 = _time.perf_counter()
    if os.environ.get("HVD_TPU_ELASTIC_SPARE") == "1":
        # A hot spare that skipped the standby barrier would rendezvous
        # as an independent world-of-1 job and could publish bogus
        # manifests into the real job's shared checkpoint directory.
        raise RuntimeError(
            "this process was launched as an elastic hot spare "
            "(HVD_TPU_ELASTIC_SPARE=1) and has not been promoted: call "
            "hvd.elastic.standby_if_spare() before hvd.init() — "
            "promotion installs the rendezvous contract and clears the "
            "flag")
    # Consume the launcher's failure stamp process-wide: only the first
    # restore after this (re)init may record recovery time (a rank that
    # resumes via state.sync() must not carry the stamp into an
    # unrelated restore hours later).
    from horovod_tpu import checkpoint_sharded as _cks
    _cks.stash_failure_stamp()
    if coordinator_address is None and num_processes is None and \
            os.environ.get("HVD_TPU_COORDINATOR"):
        # Launched by horovod_tpu.runner: pick up the rendezvous contract.
        coordinator_address = os.environ["HVD_TPU_COORDINATOR"]
        num_processes = int(os.environ["HVD_TPU_NUM_PROCESSES"])
        process_id = int(os.environ["HVD_TPU_PROCESS_ID"])
    with _LOCK:
        # Upstream reads its HOROVOD_* knob surface once at horovod_init;
        # same contract here (config.py documents the TPU-inert ones).
        from horovod_tpu import config as _config
        cfg = _config.refresh()
        if coordinator_address is not None or (
                num_processes is not None and num_processes > 1):
            # init() must stay reentrant (elastic re-init, shutdown/init
            # cycles); jax.distributed may only be initialized once.
            if not jax.distributed.is_initialized():
                jax.distributed.initialize(
                    coordinator_address=coordinator_address,
                    num_processes=num_processes,
                    process_id=process_id,
                )
        devs = tuple(devices if devices is not None else jax.devices())
        m = Mesh(np.asarray(devs, dtype=object), (axis_name,))
        # Torus discovery: HOROVOD_TOPOLOGY override wins (CPU/tests);
        # on TPU the dims come from device coords; otherwise 1-D ring.
        from horovod_tpu.parallel import mesh as _mesh_mod
        topo = _mesh_mod.detect_topology(len(devs), devs,
                                         override=cfg.topology)
        # dp x mp factoring (HOROVOD_MESH): validated against the actual
        # world and the detected torus HERE — a spec that does not factor
        # the world or nest with ICI must fail at init, not at first
        # collective. Explicit devices keep the rank map deterministic:
        # rank r sits at (dp=r//mp, mp=r%mp).
        if cfg.mesh:
            _dp, _mp = _mesh_mod.parse_mesh(cfg.mesh)
            _mesh_mod.validate_mesh(_dp, _mp, len(devs), topo)
        else:
            _dp, _mp = len(devs), 1
        m2 = _mesh_mod.make_mesh2d(_dp, _mp, devs)
        _CTX = _Context(mesh=m, axis=axis_name, devices=devs,
                        topology=topo, mesh2d=m2, mesh_dims=(_dp, _mp))
        # Reset process sets to just the global one and drop compiled
        # collectives bound to a previous mesh.
        from horovod_tpu import collective as _coll
        from horovod_tpu import process_set as _ps
        _coll._EAGER_CACHE.clear()
        _coll._reset_negotiation()
        _ps._reset_for_init(m, axis_name)
        global _INIT_EPOCH
        _INIT_EPOCH += 1
        if _INIT_EPOCH > 1:
            # Elastic re-init (or any re-mesh): every jitted program
            # retraces against the new mesh BY DESIGN, and a hot spare
            # adopting a dead rank's shard traces from scratch — neither
            # may read as recompile churn or blame an argument. Same
            # contract as the autotuner's expected=True, but epoch-wide.
            from horovod_tpu import profiler as _prof
            _prof.registry.reanchor()
        if cfg.timeline_path:
            from horovod_tpu import timeline as _tl
            if _tl.get_timeline() is None:
                _tl.start_timeline(cfg.timeline_path,
                                   mark_cycles=cfg.timeline_mark_cycles)
        # Clock-anchor for cross-rank trace alignment: every process leaves
        # this barrier at (nearly) the same instant and stamps the moment
        # into its own shard; merge_timelines aligns shards by making the
        # anchors coincide. The barrier is UNCONDITIONAL in multi-process
        # mode — gating it on this process's timeline config would deadlock
        # init when HOROVOD_TIMELINE is set on only some ranks (init is
        # already collective; one extra sync is noise). Re-inits (elastic
        # re-mesh) stamp a new epoch marker into every shard.
        from horovod_tpu import timeline as _tl
        if jax.process_count() > 1 and jax.distributed.is_initialized():
            t = _tl.get_timeline()
            if t is not None and t.rank is None:
                # Timeline was started before the distributed runtime came
                # up (start_timeline pre-init), so the path never fanned
                # out per rank — every process would stream into the SAME
                # file. Re-init onto this rank's shard (the pre-init
                # events flush to the base path).
                _tl.init_timeline(t.path)
            from jax.experimental import multihost_utils as _mhu
            _mhu.sync_global_devices("hvdtpu_timeline_anchor")
        if _tl.get_timeline() is not None:
            _tl.emit_clock_anchor(epoch=_INIT_EPOCH)
            if _INIT_EPOCH > 1:
                _tl.get_timeline().marker("elastic_epoch", category="trace",
                                          epoch=_INIT_EPOCH,
                                          world=len(devs))
        # Metrics subsystem: init span + world gauges, the snapshot
        # flusher (HOROVOD_METRICS_FILE), and the stall watchdog (unless
        # HOROVOD_STALL_CHECK_DISABLE).
        from horovod_tpu import metrics as _metrics
        _metrics.on_init(cfg, init_seconds=_time.perf_counter() - t0,
                         world=len(devs))
        # Flight recorder (HOROVOD_BLACKBOX): arm the black-box rings,
        # install the fatal-signal/excepthook dump triggers, and point
        # the stdlib faulthandler (HOROVOD_FAULTHANDLER=0 opts out) at
        # the blackbox dir for native-crash stacks.
        from horovod_tpu import blackbox as _blackbox
        _blackbox.on_init(cfg)
        # Resolved comm-knob gauges (hvd.metrics()-visible): the algorithm
        # as an info-style labeled gauge and the chunk depth. Inactive
        # algorithm labels are zeroed so a re-init with a different knob
        # leaves exactly one label at 1.
        from horovod_tpu.overlap import ALGORITHMS as _algs
        from horovod_tpu.overlap import WIRES as _wires
        for _a in _algs:
            _metrics.gauge("config_allreduce_algorithm",
                           algorithm=_a).set(
                1 if _a == cfg.allreduce_algorithm else 0)
        for _w in _wires:
            _metrics.gauge("config_allreduce_wire", wire=_w).set(
                1 if _w == cfg.allreduce_wire else 0)
        _metrics.gauge("config_overlap_chunks").set(cfg.overlap_chunks)
        # Detected torus dims, one gauge per dim index. Slots beyond the
        # detected rank are zeroed so a re-init onto a flatter fabric
        # (elastic re-mesh) does not leave stale dims —
        # hvd.doctor()'s offline _check_topology counts dims > 1 from
        # exactly these series.
        for _i in range(max(len(topo), 4)):
            _metrics.gauge("config_topology", dim=str(_i)).set(
                topo[_i] if _i < len(topo) else 0)
        # Resolved dp x mp degrees — hvd.doctor()'s _check_sharding reads
        # config_mesh_mp to tell "replicated by choice" from "sharded".
        _metrics.gauge("config_mesh_dp").set(_dp)
        _metrics.gauge("config_mesh_mp").set(_mp)
        # Exported so an OFFLINE doctor (perf_doctor over flusher files)
        # can judge checkpoint cadence against the same budget.
        _metrics.gauge("config_preemption_notice_seconds").set(
            cfg.preemption_notice_seconds)


def shutdown() -> None:
    """Tear down runtime state (``hvd.shutdown``)."""
    global _CTX
    with _LOCK:
        _CTX = None
        # Finalize an active Chrome trace — an unflushed timeline is an
        # invalid (or missing) file.
        from horovod_tpu import timeline as _tl
        _tl.shutdown_timeline()
        from horovod_tpu import collective as _coll
        from horovod_tpu import process_set as _ps
        _coll._EAGER_CACHE.clear()
        _coll._reset_negotiation()
        _ps._reset_for_shutdown()
        # Stop the watchdog/flusher threads (the flusher writes one final
        # snapshot). Metric VALUES survive shutdown — they are history,
        # not runtime state.
        from horovod_tpu import metrics as _metrics
        _metrics.on_shutdown()
        # Stop the recorder's feeds; its rings survive like metric
        # values do — a post-shutdown dump_postmortem() still works.
        from horovod_tpu import blackbox as _blackbox
        _blackbox.on_shutdown()


def is_initialized() -> bool:
    return _CTX is not None


def mesh() -> Mesh:
    """The global 1-D communicator mesh."""
    return _ctx().mesh


def mesh2d() -> Mesh:
    """The named 2-D ``("dp", "mp")`` mesh over the same devices as
    :func:`mesh` (``HOROVOD_MESH``; dp=world x mp=1 when unset)."""
    return _ctx().mesh2d


def mesh_spec() -> str:
    """The active dp x mp factoring as a ``"dpXxmpY"`` spec string."""
    from horovod_tpu.parallel.mesh import format_mesh
    dp, mp = _ctx().mesh_dims
    return format_mesh(dp, mp)


def dp_size() -> int:
    """Data-parallel degree of the active mesh (world when no mesh)."""
    return _ctx().mesh_dims[0]


def mp_size() -> int:
    """Model/tensor-parallel degree of the active mesh (1 when no mesh)."""
    return _ctx().mesh_dims[1]


def dp_rank() -> int:
    """This process's first local device's dp coordinate (host-side)."""
    ctx = _ctx()
    return _flat_rank() // ctx.mesh_dims[1]


def mp_rank() -> int:
    """This process's first local device's mp coordinate (host-side)."""
    ctx = _ctx()
    return _flat_rank() % ctx.mesh_dims[1]


def _flat_rank() -> int:
    return jax.process_index() * jax.local_device_count()


def axis_name() -> str:
    """Name of the global communicator mesh axis."""
    return _ctx().axis


def topology() -> tuple:
    """Detected torus/mesh dims of the slice, e.g. ``(4, 4)`` on a 4x4
    TPU torus or ``(2, 2)`` under ``HOROVOD_TOPOLOGY=2x2``; ``(world,)``
    when the fabric is (or is treated as) a flat 1-D ring."""
    return _ctx().topology


def topology_str() -> str:
    """:func:`topology` as an ``"XxY"`` spec string (``"8"`` for 1-D)."""
    return "x".join(str(d) for d in _ctx().topology)


def size() -> int:
    """Total number of devices in the global communicator (``hvd.size``)."""
    return len(_ctx().devices)


def local_size() -> int:
    """Devices attached to this process (``hvd.local_size``)."""
    _ctx()
    return jax.local_device_count()


def cross_size() -> int:
    """Number of host processes (``hvd.cross_size``)."""
    _ctx()
    return jax.process_count()


def cross_rank() -> int:
    """This host process's index (``hvd.cross_rank``)."""
    _ctx()
    return jax.process_index()


def rank():
    """Rank of the calling context.

    Inside a ``shard_map`` over the communicator axis this returns the
    per-device ``lax.axis_index`` (a traced value). On the host it returns the
    rank of this process's first local device, matching Horovod's
    process-level ``hvd.rank`` in the one-process-per-host TPU model.
    """
    ctx = _ctx()
    try:
        return jax.lax.axis_index(ctx.axis)
    except NameError:
        return jax.process_index() * jax.local_device_count()


def local_rank():
    """Local analogue of :func:`rank` (``hvd.local_rank``)."""
    ctx = _ctx()
    try:
        return jax.lax.axis_index(ctx.axis) % jax.local_device_count()
    except NameError:
        return 0


def in_spmd_context() -> bool:
    """True when called under tracing with the communicator axis in scope."""
    if _CTX is None:
        return False
    try:
        jax.lax.axis_index(_CTX.axis)
        return True
    except NameError:
        return False


def build_info() -> dict:
    """Capability flags (analogue of ``hvd.nccl_built``/``mpi_built`` etc.)."""
    from horovod_tpu.config import get_config
    cfg = get_config()
    backend = jax.default_backend()
    return {
        "backend": backend,
        "ici_built": backend == "tpu",
        "dcn_built": jax.process_count() > 1,
        "gloo_built": False,
        "nccl_built": False,
        "mpi_built": False,
        "pallas_built": True,
        "adasum_built": True,
        "elastic_built": True,
        # Active HOROVOD_* knob surface (config.py): the resolved values
        # plus any accepted-but-inert variables with the reason they have
        # no TPU mechanism.
        "fusion_threshold_bytes": cfg.fusion_threshold_bytes,
        "allreduce_algorithm": cfg.allreduce_algorithm,
        "allreduce_wire": cfg.allreduce_wire,
        "overlap_chunks": cfg.overlap_chunks,
        # Detected torus dims ("2x2") once init() has run; before init,
        # the HOROVOD_TOPOLOGY override if any (detection needs devices).
        "topology": (topology_str() if _CTX is not None
                     else (cfg.topology or None)),
        # Resolved dp x mp factoring ("dp8xmp1") once init() has run;
        # before init, the HOROVOD_MESH override if any (the degrees
        # need the world size to resolve).
        "mesh": (mesh_spec() if _CTX is not None else (cfg.mesh or None)),
        "mp_rules": cfg.mp_rules,
        "autotune": cfg.autotune,
        "autotune_mode": cfg.autotune_mode,
        "profile_on_stall": cfg.profile_on_stall,
        "profile_dir": cfg.profile_dir,
        "profiler_cost": cfg.profiler_cost,
        # Serving transport knobs (serving/transport.py): resolved so a
        # client and a replica can cross-check they agree on timeouts.
        "serve_rpc_timeout_seconds": cfg.serve_rpc_timeout_seconds,
        "serve_transport": cfg.serve_transport,
        # The auth token itself must never appear in logs or build_info
        # dumps — export only whether the handshake is enforced.
        "serve_auth_enabled": bool(cfg.serve_auth_token),
        "serve_max_retries": cfg.serve_max_retries,
        "serve_hedge_ms": cfg.serve_hedge_ms,
        "serve_breaker_failures": cfg.serve_breaker_failures,
        "serve_breaker_reset_seconds": cfg.serve_breaker_reset_seconds,
        # Fleet supervision knobs (serving/fleet.py): the supervisor and
        # the operator's runbook must agree on quarantine thresholds.
        "serve_fleet_restart_budget": cfg.serve_fleet_restart_budget,
        "serve_fleet_crash_loop_k": cfg.serve_fleet_crash_loop_k,
        "serve_fleet_spares": cfg.serve_fleet_spares,
        "inert_env": dict(cfg.inert),
        # Config bus (confbus.py): the mutation epoch plus the FULL
        # resolved env->value registry view — the doc/code drift test
        # holds the documented knob tables to this surface. The auth
        # token appears only as the serve_auth_enabled boolean above;
        # confbus.resolved_values() masks it the same way.
        "config_epoch": _confbus_epoch(),
        "config": _confbus_values(),
    }


def _confbus_epoch() -> int:
    try:
        from horovod_tpu import confbus
        return confbus.epoch()
    except Exception:
        return 0


def _confbus_values() -> dict:
    try:
        from horovod_tpu import confbus
        return confbus.resolved_values()
    except Exception:
        return {}
