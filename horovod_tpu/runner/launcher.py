"""Process launcher for multi-host TPU training.

Rebuild of upstream ``horovod/runner/launch.py`` + ``gloo_run.py``. The
reference spawns ``np`` worker processes (ssh for remote hosts) and stands up
a gloo rendezvous server. The TPU model is one process per host (each process
drives all local chips), with ``jax.distributed`` as the rendezvous — the
coordinator address plays the role of the reference's rendezvous server.

Local mode (``hosts=None``): spawn ``np`` processes on this machine. One
worker keeps the ambient platform (on a TPU host, the TPU); several are
forced to ``JAX_PLATFORMS=cpu`` (they cannot share one accelerator) — a
CPU-only mode for framework testing, like the reference's
``horovodrun -np 4 -H localhost:4``.
Remote mode emits per-host launch commands (ssh execution is environment
policy; TPU pods normally launch via the cloud tooling, e.g. one command on
every TPU-VM worker).
"""

from __future__ import annotations

import dataclasses
import logging
import os
import shlex
import subprocess
import sys
from typing import Dict, List, Optional, Sequence

logger = logging.getLogger("horovod_tpu")

__all__ = ["HostSpec", "parse_hosts", "build_worker_env", "worker_commands",
           "run", "run_func", "run_elastic"]

DEFAULT_PORT = 29500


@dataclasses.dataclass
class HostSpec:
    host: str
    slots: int


def parse_hosts(hosts: str) -> List[HostSpec]:
    """Parse ``"host1:4,host2:4"`` (upstream ``parse_hosts``) or a hostfile
    path with ``host slots=N`` lines (upstream ``parse_host_files``)."""
    specs: List[HostSpec] = []
    if os.path.isfile(hosts):
        with open(hosts) as f:
            for line in f:
                line = line.split("#")[0].strip()
                if not line:
                    continue
                parts = line.split()
                slots = 1
                for p in parts[1:]:
                    if p.startswith("slots="):
                        slots = int(p.split("=", 1)[1])
                specs.append(HostSpec(parts[0], slots))
        return specs
    for item in hosts.split(","):
        item = item.strip()
        if not item:
            continue
        if ":" in item:
            h, s = item.rsplit(":", 1)
            specs.append(HostSpec(h, int(s)))
        else:
            specs.append(HostSpec(item, 1))
    return specs


def build_worker_env(process_id: int, num_processes: int,
                     coordinator: str, base_env: Optional[Dict] = None) -> Dict:
    """Environment for one worker process; horovod_tpu.init() picks these up
    (mirrors the reference's HOROVOD_RANK/SIZE env contract)."""
    env = dict(base_env if base_env is not None else os.environ)
    env.update({
        "HVD_TPU_COORDINATOR": coordinator,
        "HVD_TPU_NUM_PROCESSES": str(num_processes),
        "HVD_TPU_PROCESS_ID": str(process_id),
    })
    return env


def worker_commands(command: Sequence[str], hosts: List[HostSpec],
                    coordinator_port: int = DEFAULT_PORT,
                    extra_env: Optional[Dict[str, str]] = None) -> List[str]:
    """One launch command per host for remote mode (the user or cloud tooling
    executes them; the reference would ssh). ``extra_env`` rides the env
    prefix of every line."""
    coordinator = f"{hosts[0].host}:{coordinator_port}"
    extras = "".join(f"{k}={shlex.quote(v)} "
                     for k, v in (extra_env or {}).items())
    cmds = []
    for pid, spec in enumerate(hosts):
        env = (f"{extras}HVD_TPU_COORDINATOR={coordinator} "
               f"HVD_TPU_NUM_PROCESSES={len(hosts)} "
               f"HVD_TPU_PROCESS_ID={pid}")
        cmds.append(f"{env} {' '.join(shlex.quote(c) for c in command)}")
    return cmds


def local_ip() -> str:
    """Best-effort address other hosts can reach this machine on (upstream
    ``horovod/runner/driver/driver_service.py`` interface discovery): the
    UDP-connect trick finds the interface with a default route; falls back
    to the hostname's address."""
    import socket
    try:
        with socket.socket(socket.AF_INET, socket.SOCK_DGRAM) as s:
            s.connect(("10.255.255.255", 1))
            return s.getsockname()[0]
    except OSError:
        try:
            return socket.gethostbyname(socket.gethostname())
        except OSError:
            return "127.0.0.1"


def _ssh_argv(host: str, line: str) -> List[str]:
    """argv to execute ``line`` on ``host`` (upstream gloo_run's ssh
    execution). BatchMode so a missing key fails instead of prompting;
    ``-tt`` forces a pty so terminating the local ssh client HUPs the
    remote process group — without it fail-fast teardown would orphan
    remote workers blocked in rendezvous."""
    return ["ssh", "-tt", "-o", "BatchMode=yes",
            "-o", "StrictHostKeyChecking=no", host, line]


def _supervise(procs: List[subprocess.Popen],
               timeout: Optional[float]) -> int:
    """Wait for workers; any worker failing must take down its peers —
    otherwise survivors block forever in rendezvous waiting for the dead
    rank (the reference kills the job on first worker failure too)."""
    import time
    rc = 0
    timed_out = False
    deadline = None if timeout is None else time.monotonic() + timeout
    try:
        pending = list(procs)
        while pending and rc == 0:
            for p in list(pending):
                code = p.poll()
                if code is None:
                    continue
                pending.remove(p)
                if code:
                    rc = code
                    break
            if pending and rc == 0 and deadline is not None and \
                    time.monotonic() > deadline:
                timed_out = True
                break
            time.sleep(0.05)
    finally:
        for p in procs:
            if p.poll() is None:
                p.terminate()
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
    if timed_out:
        raise TimeoutError(
            f"workers still running after {timeout}s; job killed")
    if rc:
        raise RuntimeError(f"worker exited with code {rc}")
    return 0


def _rank_output(output_filename: Optional[str], rank: int):
    """Per-rank log sink (upstream ``horovodrun --output-filename``:
    ``<dir>/rank.<N>/stdout``). None = inherit the launcher's streams."""
    if output_filename is None:
        return None
    d = os.path.join(output_filename, f"rank.{rank}")
    os.makedirs(d, exist_ok=True)
    return open(os.path.join(d, "stdout"), "wb")


def _force_cpu_if_shared(env: Dict[str, str], np: int) -> None:
    """Platform policy for workers on ONE host. A chip belongs to one
    process, so several local workers are a CPU-only test mode and are
    forced there (``extra_env`` can override); a single worker keeps the
    ambient platform — on a TPU host that is the TPU."""
    if np > 1:
        if env.get("JAX_PLATFORMS") != "cpu":
            logger.info("%d workers on one host cannot share its accelerator: "
                        "forcing JAX_PLATFORMS=cpu for them", np)
        env["JAX_PLATFORMS"] = "cpu"


def run(command: Sequence[str], np: int = 1, hosts: Optional[str] = None,
        coordinator_port: int = DEFAULT_PORT, dry_run: bool = False,
        extra_env: Optional[Dict[str, str]] = None,
        timeout: Optional[float] = None, ssh: bool = False,
        output_filename: Optional[str] = None):
    """``horovodrun`` equivalent.

    - ``hosts=None``: spawn ``np`` local worker processes and wait.
    - ``hosts="h1:8,h2:8"``: per-host launch. With ``ssh=True`` the
      launcher executes one command per host over ssh and supervises them
      (upstream ``gloo_run``); otherwise it prints/returns the commands for
      the user or cloud tooling to run (TPU pods normally launch via the
      provider's one-command-per-VM tooling).
    - ``dry_run``: return commands without executing.
    - ``timeout``: kill the job and raise if workers are still running after
      this many seconds (upstream ``--start-timeout``'s role: a wedged
      rendezvous or accelerator runtime turns into an error, not a silent
      infinite hang).
    - ``output_filename``: directory for per-rank logs
      (``<dir>/rank.<N>/stdout``, stderr merged — upstream
      ``--output-filename``).
    """
    if hosts is not None:
        specs = parse_hosts(hosts)
        cmds = worker_commands(command, specs, coordinator_port,
                               extra_env=extra_env)
        if dry_run:
            return cmds
        if not ssh:
            for c in cmds:
                print(c)
            return cmds
        procs = []
        for rank, (spec, line) in enumerate(zip(specs, cmds)):
            sink = _rank_output(output_filename, rank)
            procs.append(subprocess.Popen(
                _ssh_argv(spec.host, line), stdout=sink,
                stderr=subprocess.STDOUT if sink else None))
            if sink is not None:
                sink.close()   # the child holds its own duplicate fd
        return _supervise(procs, timeout)

    coordinator = f"127.0.0.1:{coordinator_port}"
    if dry_run:
        return [" ".join(command)] * np
    procs = []
    for pid in range(np):
        env = build_worker_env(pid, np, coordinator,
                               base_env=dict(os.environ))
        _force_cpu_if_shared(env, np)
        if extra_env:
            env.update(extra_env)
        sink = _rank_output(output_filename, pid)
        procs.append(subprocess.Popen(
            list(command), env=env, stdout=sink,
            stderr=subprocess.STDOUT if sink else None))
        if sink is not None:
            sink.close()   # the child holds its own duplicate fd
    return _supervise(procs, timeout)


def run_elastic(command: Sequence[str], np: int = 2, min_np: int = 1,
                max_restarts: int = 3,
                coordinator_port: int = DEFAULT_PORT,
                state_dir: Optional[str] = None,
                extra_env: Optional[Dict[str, str]] = None,
                timeout: Optional[float] = None,
                discovery=None, max_np: Optional[int] = None,
                spares: int = 0) -> int:
    """Fault-tolerant multi-process launch (upstream
    ``horovod/runner/elastic/driver.py``).

    Spawns ``np`` workers; when one dies, the whole job is torn down and
    relaunched over the survivors (world shrinks by the number of failed
    workers) with a fresh coordinator — a new ``jax.distributed`` world
    cannot be re-formed inside a live process, so process restart IS the
    recovery mechanism on TPU (host preemption kills every process on the
    host anyway). Workers persist their last ``JaxState`` commit via
    ``state.save(path)`` under ``state_dir`` (exported as
    ``HVD_TPU_ELASTIC_STATE_DIR``) and restore + ``sync()`` it on entry;
    ``HVD_TPU_ELASTIC_RESTART`` carries the attempt number.

    Stops when a relaunch would drop below ``min_np`` or after
    ``max_restarts`` attempts; returns the number of restarts on success.

    ``discovery``: optional zero-arg callable returning the currently
    available slot count (upstream ``--host-discovery-script``); consulted
    between attempts so recovered capacity scales the relaunch back up,
    capped at ``max_np`` (default: ``np`` — slots beyond what was asked
    for were never provisioned; elastic executors that may START below
    their provision cap pass ``max_np`` explicitly). Without it the world
    only shrinks (survivors).

    ``spares``: hot-spare processes provisioned alongside the job
    (``HVD_TPU_ELASTIC_SPARE=1``): each runs the same command, registers
    with discovery, and idles in ``hvd.elastic.standby_if_spare()`` until
    a worker dies — then it is *promoted* into the dead rank's slot so
    the relaunched world keeps its size (instead of shrinking to the
    survivors), adopting the dead rank's optimizer shard from the last
    sharded-checkpoint manifest (docs/ELASTIC.md). The spare pool is not
    replenished; once spent, further failures shrink the world as before.
    """
    import tempfile
    import time

    if timeout is None and os.environ.get("HOROVOD_ELASTIC_TIMEOUT"):
        # Upstream's elastic rendezvous timeout; the closest analogue in
        # the relaunch model is the per-attempt job deadline. Only applied
        # when the user set the variable — an unset default must not kill
        # long jobs. Read the env var directly so a value set after
        # init()'s config snapshot still applies.
        timeout = float(os.environ["HOROVOD_ELASTIC_TIMEOUT"])
    if state_dir is None:
        state_dir = tempfile.mkdtemp(prefix="hvd_tpu_elastic_")

    def _spawn_spare(idx: int):
        # Launcher-assigned identity token: the registering interpreter
        # may be a grandchild of the Popen handle (command wrapped in a
        # shell script), so the promote handshake cannot assume
        # Popen.pid == os.getpid() of the process that calls standby().
        token = f"spare-{os.getpid()}-{idx}"
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["HVD_TPU_ELASTIC_SPARE"] = "1"
        env["HVD_TPU_ELASTIC_SPARE_ID"] = token
        env["HVD_TPU_ELASTIC_STATE_DIR"] = state_dir
        if extra_env:
            env.update(extra_env)
        return subprocess.Popen(list(command), env=env), token

    spare_pool = [_spawn_spare(i) for i in range(max(0, spares))]
    world = np
    restarts = 0
    promoted: list = []   # [(Popen, rank)] carried into the next attempt
    failed_at: Optional[float] = None
    try:
        while True:
            coordinator = f"127.0.0.1:{coordinator_port + restarts}"
            procs = []
            taken = {r for _, r in promoted}
            fresh_ranks = [r for r in range(world) if r not in taken]
            for pid in fresh_ranks:
                env = build_worker_env(pid, world, coordinator,
                                       base_env=dict(os.environ))
                _force_cpu_if_shared(env, world)
                env["HVD_TPU_ELASTIC_STATE_DIR"] = state_dir
                env["HVD_TPU_ELASTIC_RESTART"] = str(restarts)
                if failed_at is not None:
                    # Recovery-time anchor: workers (and the doctor)
                    # measure death -> restored from this stamp.
                    env["HVD_TPU_ELASTIC_FAILED_AT"] = str(failed_at)
                if extra_env:
                    env.update(extra_env)
                procs.append(subprocess.Popen(list(command), env=env))
            procs.extend(p for p, _ in promoted)
            promoted = []

            failed = 0
            deadline = (None if timeout is None
                        else time.monotonic() + timeout)
            pending = list(procs)
            while pending and not failed:
                for p in list(pending):
                    code = p.poll()
                    if code is None:
                        continue
                    pending.remove(p)
                    if code:
                        failed += 1
                # A spare dying is capacity loss, not job failure.
                for entry in list(spare_pool):
                    if entry[0].poll() is not None:
                        spare_pool.remove(entry)
                        logger.warning("elastic: spare %s exited "
                                       "(%d spare(s) left)", entry[1],
                                       len(spare_pool))
                if pending and deadline is not None and \
                        time.monotonic() > deadline:
                    for p in procs:
                        if p.poll() is None:
                            p.kill()
                    raise TimeoutError(
                        f"elastic workers still running after {timeout}s")
                time.sleep(0.05)

            if not failed:
                return restarts
            failed_at = time.time()

            # A worker died: tear the job down (survivors are blocked on
            # the dead rank's collectives) and relaunch over the remaining
            # world.
            for p in procs:
                if p.poll() is None:
                    p.terminate()
            for p in procs:
                try:
                    p.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    p.kill()
            # Only organically-failed workers (nonzero exit before
            # teardown) count as lost hosts; survivors we terminated
            # relaunch.
            world = world - failed
            if discovery is not None:
                # Upstream's host-discovery hook (--host-discovery-script
                # / elastic driver polling): consult it between attempts
                # so recovered capacity scales the job back UP, capped at
                # the provision limit (max_np, defaulting to the original
                # np).
                try:
                    world = max(world, min(int(discovery()), max_np or np))
                except Exception as e:
                    logger.warning("elastic discovery hook failed (%s); "
                                   "continuing with world=%d", e, world)
            restarts += 1
            # Hot-spare promotion: refill lost slots from the standby
            # pool so the relaunched world keeps its size; the promoted
            # spare joins the new rendezvous in the dead rank's slot and
            # adopts its shard from the last manifest (docs/ELASTIC.md).
            if spare_pool and world < (max_np or np):
                from horovod_tpu.elastic import driver as _edriver
                # Promote only spares that are ALIVE and have actually
                # reached standby() (registration heartbeat fresh): a
                # dead spare would burn a restart on an instant failure,
                # and a wedged one that never registered would leave the
                # relaunched rendezvous waiting for a rank that never
                # joins until the elastic timeout.
                registered = set(_edriver.list_spares(state_dir))
                ready = [e for e in spare_pool
                         if e[0].poll() is None and e[1] in registered]
                n_promote = min(len(ready), (max_np or np) - world)
                next_world = world + n_promote
                next_coord = f"127.0.0.1:{coordinator_port + restarts}"
                for i in range(n_promote):
                    p, token = ready[i]
                    spare_pool.remove(ready[i])
                    rank = world + i   # highest ranks of the new world
                    _edriver.promote_spare(
                        state_dir, token, rank=rank,
                        world=next_world, coordinator=next_coord,
                        restart=restarts, failed_at=failed_at)
                    promoted.append((p, rank))
                world = next_world
            if world < min_np:
                raise RuntimeError(
                    f"elastic job below min_np: {world} < {min_np} after "
                    f"{restarts} restart(s)")
            if restarts > max_restarts:
                raise RuntimeError(
                    f"elastic job exceeded max_restarts={max_restarts}")
    finally:
        for p in [e[0] for e in spare_pool] + [p for p, _ in promoted]:
            if p.poll() is None:
                p.kill()


_FUNC_WORKER = """\
import os, sys
import cloudpickle
with open(sys.argv[1], "rb") as f:
    fn, args, kwargs = cloudpickle.loads(f.read())
import horovod_tpu as hvd
hvd.init()   # picks up the HVD_TPU_* rendezvous contract from the env
result = fn(*args, **kwargs)
rank = os.environ["HVD_TPU_PROCESS_ID"]
with open(os.path.join(sys.argv[2], "result_" + rank + ".pkl"), "wb") as f:
    cloudpickle.dump(result, f)
"""


def run_func(fn, args: tuple = (), kwargs: Optional[Dict] = None,
             np: int = 1, coordinator_port: int = DEFAULT_PORT,
             extra_env: Optional[Dict[str, str]] = None,
             timeout: Optional[float] = None) -> list:
    """Programmatic launcher (upstream ``horovod.run``): execute ``fn`` on
    ``np`` worker processes and return ``[fn's result per rank]``.

    Workers rendezvous through ``jax.distributed`` (each calls
    ``hvd.init()`` on entry, exactly as a script launched by ``run`` would);
    ``fn`` is shipped with cloudpickle so closures and lambdas work. Local
    workers default to the CPU backend — they cannot share one accelerator.
    """
    import tempfile

    import cloudpickle

    with tempfile.TemporaryDirectory(prefix="hvd_tpu_runfunc_") as td:
        fn_path = os.path.join(td, "fn.pkl")
        with open(fn_path, "wb") as f:
            f.write(cloudpickle.dumps((fn, args, kwargs or {})))
        command = [sys.executable, "-c", _FUNC_WORKER, fn_path, td]
        run(command, np=np, coordinator_port=coordinator_port,
            extra_env=extra_env, timeout=timeout)
        results = []
        for rank in range(np):
            path = os.path.join(td, f"result_{rank}.pkl")
            if not os.path.exists(path):
                raise RuntimeError(
                    f"worker {rank} produced no result (crashed after "
                    "rendezvous?)")
            with open(path, "rb") as f:
                results.append(cloudpickle.load(f))
        return results


def main(argv: Optional[List[str]] = None) -> int:
    """CLI: ``python -m horovod_tpu.runner -np 4 python train.py``."""
    import argparse
    parser = argparse.ArgumentParser(
        prog="hvdrun-tpu",
        description="Launch horovod_tpu workers (horovodrun equivalent)")
    parser.add_argument("-np", "--num-proc", type=int, default=1)
    parser.add_argument("-H", "--hosts", default=None,
                        help='e.g. "host1:8,host2:8" or a hostfile path')
    parser.add_argument("--port", type=int, default=DEFAULT_PORT)
    parser.add_argument("--start-timeout", type=float, default=None,
                        help="kill the job if workers are still running "
                             "after this many seconds")
    parser.add_argument("--ssh", action="store_true",
                        help="execute the per-host commands over ssh and "
                             "supervise them (upstream gloo_run)")
    parser.add_argument("--output-filename", default=None,
                        help="directory for per-rank logs "
                             "(<dir>/rank.N/stdout, stderr merged; "
                             "upstream --output-filename)")
    parser.add_argument("--dry-run", action="store_true")
    parser.add_argument("--check-build", action="store_true",
                        help="print capability flags and exit "
                             "(horovodrun --check-build)")
    parser.add_argument("command", nargs=argparse.REMAINDER)
    args = parser.parse_args(argv)
    if args.check_build:
        # No init(): the diagnostic must work even when the rendezvous
        # would block or the accelerator is held (upstream --check-build
        # prints build flags without initializing); build_info only reads
        # the jax backend + config.
        import json as _json

        import horovod_tpu as _hvd
        print(_json.dumps(_hvd.build_info(), indent=2, default=str))
        return 0
    if args.command and args.command[0] == "--":
        args.command = args.command[1:]
    if not args.command:
        parser.error("no command given")
    out = run(args.command, np=args.num_proc, hosts=args.hosts,
              coordinator_port=args.port, dry_run=args.dry_run,
              timeout=args.start_timeout, ssh=args.ssh,
              output_filename=args.output_filename)
    if args.dry_run and isinstance(out, list):
        for c in out:
            print(c)
        return 0
    return out if isinstance(out, int) else 0


if __name__ == "__main__":
    sys.exit(main())
