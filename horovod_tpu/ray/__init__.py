"""Ray integration (upstream ``horovod/ray/runner.py:RayExecutor``).

The executor state machine — place N rendezvoused workers, run functions on
all of them, collect per-rank results, tear down — is implemented against
the injected :class:`horovod_tpu.cluster.ClusterBackend`, so it works (and
is tested) without the ray package: the default backend is
``LocalProcessBackend`` (real processes + jax.distributed rendezvous). When
ray *is* importable, ``RayBackend`` schedules the same contract over ray
tasks; on a TPU pod the natural backend is one worker per TPU-VM host.
"""

from __future__ import annotations

from concurrent.futures import Future, ThreadPoolExecutor
from typing import Any, Callable, Dict, List, Optional

from horovod_tpu.cluster import ClusterBackend, LocalProcessBackend

__all__ = ["RayExecutor", "RayBackend", "ElasticRayExecutor",
           "RayHostDiscovery", "ray_available", "run_remote"]


def run_remote(*_a, **_k):
    """Upstream module-level ``horovod.ray.run_remote`` surface — here the
    async path is a method: ``RayExecutor(...).run_remote(fn)``."""
    raise RuntimeError(
        "horovod_tpu.ray.run_remote: construct a RayExecutor and call "
        "executor.run_remote(fn) (returns a Future; .result() replaces "
        "ray.get)")


def ray_available() -> bool:
    try:
        import ray  # noqa: F401
        return True
    except ImportError:
        return False


class RayBackend(ClusterBackend):
    """ClusterBackend over ray remote tasks (requires the ray package).

    Each worker is a ray task pinned by ``resources_per_worker``; the
    rendezvous env (coordinator address + rank) is injected exactly as
    ``runner.run_func`` does locally.
    """

    def __init__(self, num_workers: int,
                 resources_per_worker: Optional[Dict] = None,
                 coordinator_port: int = 29800):
        if not ray_available():
            raise RuntimeError(
                "RayBackend requires the ray package; inject "
                "LocalProcessBackend (or any ClusterBackend) instead on "
                "environments without ray")
        self.num_workers = num_workers
        self._resources = resources_per_worker or {}
        self._port = coordinator_port

    def run(self, fn, args=(), kwargs=None, env=None):
        import ray

        n = self.num_workers
        port = self._port

        # Rank 0 binds the jax.distributed coordinator, so its address must
        # be *rank 0's node*, not the driver's: rank 0 runs inside an actor
        # whose routable IP is queried first, then everyone (actor included)
        # rendezvouses against it (upstream RayExecutor resolves the nics of
        # its actor group the same way).
        @ray.remote
        class _Rank0:
            def ip(self):
                from horovod_tpu.runner.launcher import local_ip
                return local_ip()

            def work(self, coordinator):
                _enter(coordinator, 0)
                return fn(*args, **(kwargs or {}))

        def _enter(coordinator, pid):
            import os
            os.environ.update(env or {})
            os.environ["HVD_TPU_COORDINATOR"] = coordinator
            os.environ["HVD_TPU_NUM_PROCESSES"] = str(n)
            os.environ["HVD_TPU_PROCESS_ID"] = str(pid)
            import horovod_tpu as hvd
            hvd.init()

        @ray.remote
        def _worker(coordinator, pid: int):
            _enter(coordinator, pid)
            return fn(*args, **(kwargs or {}))

        opts = {"resources": self._resources} if self._resources else {}
        rank0 = _Rank0.options(**opts).remote()
        coordinator = f"{ray.get(rank0.ip.remote())}:{port}"
        futs = [rank0.work.remote(coordinator)]
        worker = _worker.options(**opts)
        futs += [worker.remote(coordinator, pid) for pid in range(1, n)]
        return ray.get(futs)


class RayHostDiscovery:
    """Slot discovery from the live ray cluster (upstream
    ``horovod/ray/elastic_v2.py:RayHostDiscovery``): each alive node
    contributes ``CPU // cpus_per_slot`` (or ``GPU // gpus_per_slot``)
    worker slots.

    ``nodes_fn`` is injectable — tests (and ray-less environments)
    simulate node loss/recovery by swapping the node list; the default
    queries ``ray.nodes()``.
    """

    def __init__(self, use_gpu: bool = False, cpus_per_slot: int = 1,
                 gpus_per_slot: int = 1,
                 nodes_fn: Optional[Callable[[], list]] = None):
        if nodes_fn is None:
            if not ray_available():
                raise RuntimeError(
                    "RayHostDiscovery without the ray package needs an "
                    "injected nodes_fn")

            def nodes_fn():
                import ray
                return ray.nodes()
        self._nodes_fn = nodes_fn
        self._use_gpu = use_gpu
        self._cpus = max(cpus_per_slot, 1)
        self._gpus = max(gpus_per_slot, 1)

    def __call__(self) -> int:
        slots = 0
        for node in self._nodes_fn():
            if not node.get("Alive", False):
                continue
            res = node.get("Resources", {}) or {}
            if self._use_gpu:
                slots += int(res.get("GPU", 0)) // self._gpus
            else:
                slots += int(res.get("CPU", 0)) // self._cpus
        return slots


# Worker bootstrap for ElasticRayExecutor.run(worker_fn): rendezvous via
# the run_elastic env contract and call the pickled fn.
_ELASTIC_BOOTSTRAP = """\
import os, sys
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=1")
import cloudpickle
with open(sys.argv[1], "rb") as f:
    fn = cloudpickle.load(f)
import horovod_tpu as hvd
hvd.init()
fn()
"""


class ElasticRayExecutor:
    """``horovod.ray.ElasticRayExecutor`` parity
    (``horovod/ray/elastic_v2.py``): an elastic job whose between-attempt
    world size comes from ray host discovery.

    Upstream keeps long-lived actors and rebuilds the NCCL ring in place;
    on TPU a ``jax.distributed`` world cannot be re-formed inside live
    processes, so worker/actor death tears the attempt down and
    ``runner.run_elastic`` relaunches over however many slots
    ``discovery`` currently reports (capped at ``max_workers``, floored
    at ``min_workers`` — below that the job fails). Workers resume from
    their last committed elastic ``State`` exactly as in the relaunch
    tests (``tests/test_elastic_relaunch.py``).

    ``discovery`` defaults to :class:`RayHostDiscovery` over live
    ``ray.nodes()``; inject any zero-arg callable returning a slot count
    to run without ray (tests simulate actor loss this way).
    """

    def __init__(self, settings: Optional[Any] = None,
                 min_workers: int = 1, max_workers: int = 2,
                 max_restarts: int = 3,
                 use_gpu: bool = False, cpus_per_slot: int = 1,
                 gpus_per_slot: int = 1,
                 discovery: Optional[Callable[[], int]] = None,
                 state_dir: Optional[str] = None,
                 coordinator_port: int = 29860):
        if discovery is None:
            discovery = RayHostDiscovery(use_gpu=use_gpu,
                                         cpus_per_slot=cpus_per_slot,
                                         gpus_per_slot=gpus_per_slot)
        self.discovery = discovery
        self.min_workers = min_workers
        self.max_workers = max_workers
        self.max_restarts = max_restarts
        self.state_dir = state_dir
        self.settings = settings
        self._port = coordinator_port
        self._started = False

    def _slots(self, floor: bool) -> int:
        """Discovered slots capped at max_workers. ``floor=True`` (initial
        spawn) also floors at min_workers — at least min are attempted;
        the RELAUNCH path must NOT floor, so a cluster that truly lost
        capacity below min_workers fails fast via run_elastic's min_np
        check instead of relaunching workers that have nowhere to run."""
        slots = min(int(self.discovery()), self.max_workers)
        return max(slots, self.min_workers) if floor else slots

    def start(self) -> None:
        """Resolve the initial world from discovery (upstream queries the
        actor group here)."""
        self._initial = self._slots(floor=True)
        self._started = True

    def run(self, worker_fn: Optional[Callable] = None,
            command: Optional[list] = None,
            extra_env: Optional[Dict[str, str]] = None,
            timeout: Optional[float] = None) -> int:
        """Run the elastic job; returns the restart count.

        Either a picklable zero-arg ``worker_fn`` (run on every worker
        with hvd initialized — the upstream surface) or an explicit argv
        ``command``. Worker loss -> teardown -> relaunch over
        ``discovery()`` slots; state recovery is the worker's job via the
        elastic ``State`` save/load/sync contract.
        """
        if not self._started:
            raise RuntimeError("ElasticRayExecutor.start() must be called "
                               "before run() (upstream contract)")
        if (worker_fn is None) == (command is None):
            raise ValueError("pass exactly one of worker_fn= or command=")
        from horovod_tpu.runner.launcher import run_elastic

        import shutil
        import sys as _sys
        import tempfile
        own_dir = self.state_dir is None
        state_dir = self.state_dir or tempfile.mkdtemp(
            prefix="hvd_tpu_elastic_ray_")
        try:
            if worker_fn is not None:
                import cloudpickle
                import os as _os
                payload = _os.path.join(state_dir, "worker_fn.pkl")
                with open(payload, "wb") as f:
                    f.write(cloudpickle.dumps(worker_fn))
                command = [_sys.executable, "-c", _ELASTIC_BOOTSTRAP,
                           payload]
            return run_elastic(
                command, np=self._initial, min_np=self.min_workers,
                max_np=self.max_workers,
                max_restarts=self.max_restarts,
                coordinator_port=self._port, state_dir=state_dir,
                extra_env=extra_env, timeout=timeout,
                discovery=lambda: self._slots(floor=False))
        finally:
            if own_dir:
                # Nothing outside this call can reach an implicitly
                # created dir (pickled closures can embed large arrays) —
                # don't leak one per run.
                shutil.rmtree(state_dir, ignore_errors=True)

    def shutdown(self) -> None:
        self._started = False


class RayExecutor:
    """``horovod.ray.RayExecutor`` parity: start N workers, run functions
    on all of them, collect per-rank results.

    Differences from upstream are TPU-model driven: workers are processes
    that rendezvous through jax.distributed (not long-lived ray actors
    holding NCCL comms), so each ``run`` forms a fresh world — which is
    also what makes the executor elastic-friendly (see
    ``runner.run_elastic``).
    """

    def __init__(self, settings: Optional[Any] = None,
                 num_workers: Optional[int] = None,
                 cpus_per_worker: int = 1, use_gpu: bool = False,
                 gpus_per_worker: int = 0,
                 backend: Optional[ClusterBackend] = None):
        if backend is None:
            n = num_workers or 1
            backend = RayBackend(n) if ray_available() \
                else LocalProcessBackend(n)
        self.backend = backend
        self.num_workers = backend.num_workers
        self.settings = settings
        self._started = False
        self._pool: Optional[ThreadPoolExecutor] = None

    def start(self, extras: Optional[Dict] = None) -> None:
        self.backend.start()
        self._started = True

    def _require_started(self):
        if not self._started:
            raise RuntimeError(
                "RayExecutor.start() must be called before run/execute "
                "(upstream contract)")

    def run(self, fn: Callable, args: tuple = (),
            kwargs: Optional[Dict] = None) -> List[Any]:
        """Run ``fn`` on every worker (hvd initialized); per-rank results."""
        self._require_started()
        return self.backend.run(fn, args=args, kwargs=kwargs)

    def run_remote(self, fn: Callable, args: tuple = (),
                   kwargs: Optional[Dict] = None) -> Future:
        """Async variant: a Future resolving to the per-rank results
        (upstream returns ray ObjectRefs; a Future is the scheduler-neutral
        equivalent — ``.result()`` replaces ``ray.get``)."""
        self._require_started()
        if self._pool is None:
            self._pool = ThreadPoolExecutor(max_workers=1)
        return self._pool.submit(self.backend.run, fn, args, kwargs)

    def execute(self, fn: Callable) -> List[Any]:
        """Run a zero-arg callable on every worker (upstream
        ``RayExecutor.execute``)."""
        return self.run(fn)

    def execute_single(self, fn: Callable) -> Any:
        """Run on rank 0 only and return its result (upstream
        ``execute_single``): every worker joins the rendezvous, only rank
        0 evaluates the callable."""

        def on_rank0():
            import jax
            return fn() if jax.process_index() == 0 else None

        return self.run(on_rank0)[0]

    def shutdown(self) -> None:
        if self._pool is not None:
            self._pool.shutdown(wait=True)
            self._pool = None
        self.backend.shutdown()
        self._started = False
