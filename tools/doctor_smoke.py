#!/usr/bin/env python
"""Doctor smoke: 2 CPU processes, a manufactured straggler and a forced
recompile, one ranked diagnosis.

Spawns two real processes that rendezvous over ``jax.distributed`` with
``HOROVOD_TIMELINE`` shards on. Rank 1 sleeps 750ms before one allreduce
(manufactured straggler: long enough that the scheduling noise of a
loaded machine, which has eaten 120ms of it, leaves the 200ms floor);
both ranks run a profiled step twice with a changed static argument
(forced recompile, blamed on ``seq_len``); each rank writes its metrics
snapshot. The parent merges the trace shards, fuses the snapshots, runs
``hvd.doctor()``, and verifies:

* a ``straggler`` finding names rank 1 with >= 200ms of blame,
* a ``recompile`` finding names the blamed argument ``seq_len``,
* findings are ranked (severities non-increasing).

Exit status 0 = all checks pass. Wired as tier-1
(``tests/test_doctor.py``) and as ``make doctor-smoke``.
"""

import json
import os
import socket
import subprocess
import sys
import tempfile
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WORKER = textwrap.dedent("""
    import os, sys, time
    import jax
    jax.config.update("jax_platforms", "cpu")
    pid, port, trace, metfile = (int(sys.argv[1]), sys.argv[2],
                                 sys.argv[3], sys.argv[4])
    sys.path.insert(0, {repo!r})
    os.environ["HOROVOD_TIMELINE"] = trace
    import numpy as np
    import horovod_tpu as hvd
    from horovod_tpu import profiler
    hvd.init(coordinator_address=f"127.0.0.1:{{port}}", num_processes=2,
             process_id=pid)
    assert jax.process_count() == 2
    n = hvd.size()
    for step in range(3):
        if pid == 1 and step == 1:
            time.sleep(0.75)   # manufactured straggler: rank 1 arrives late
        hvd.allreduce(np.full((n, 4), float(pid + 1), np.float32),
                      name=f"grads_step{{step}}")
    # Forced recompile: the static seq_len changes between calls, so the
    # fingerprint detector must count it and blame the argument by name.
    tstep = profiler.instrument(
        lambda x, seq_len: x[:seq_len] * 2.0, name="train_step",
        static_argnums=(1,))
    x = np.arange(8.0, dtype=np.float32)
    tstep(x, 8)
    tstep(x, 4)
    rec = tstep.record()
    assert rec.recompiles == 1 and rec.last_blame == ["seq_len"], (
        rec.recompiles, rec.last_blame)
    with open(metfile, "w") as f:
        f.write(hvd.metrics.to_json())
    hvd.shutdown()
    print(f"proc {{pid}} DOCTOR-OK", flush=True)
""").format(repo=REPO)


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def run_smoke(workdir: str, timeout_s: float = 240.0):
    """One attempt: returns ``(rc, failure_text)``; a rendezvous-flavored
    failure text gets the attempt retried by ``smoke_util``."""
    trace = os.path.join(workdir, "trace.json")
    metfiles = [os.path.join(workdir, f"metrics.r{r}.json") for r in (0, 1)]
    port = _free_port()
    procs = [subprocess.Popen(
        [sys.executable, "-c", WORKER, str(pid), str(port), trace,
         metfiles[pid]],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
        for pid in range(2)]
    outs = [p.communicate(timeout=timeout_s)[0] for p in procs]
    for p, out in zip(procs, outs):
        if p.returncode != 0 or "DOCTOR-OK" not in out:
            print(f"worker failed (rc={p.returncode}):\n{out}",
                  file=sys.stderr)
            return 1, "\n".join(outs)

    sys.path.insert(0, REPO)
    sys.path.insert(0, os.path.join(REPO, "tools"))
    from perf_doctor import _merge_snapshots

    from horovod_tpu.profiler import doctor, format_report
    from horovod_tpu.trace_merge import merge_timelines

    merged = merge_timelines(trace, os.path.join(workdir, "merged.json"),
                             feed_metrics=False)
    snapshot = _merge_snapshots(metfiles)
    report = doctor(snapshot=snapshot, trace=merged, programs={})
    print(format_report(report))
    findings = report["findings"]

    sev = [f["severity"] for f in findings]
    if sev != sorted(sev, reverse=True):
        print(f"findings are not ranked: {sev}", file=sys.stderr)
        return 1, ""

    stragglers = [f for f in findings if f["category"] == "straggler"]
    if not stragglers:
        print("no straggler finding", file=sys.stderr)
        return 1, ""
    s = stragglers[0]
    if s["evidence"].get("blamed_rank") != 1 \
            or s["evidence"].get("blame_seconds", 0) < 0.2:
        print(f"straggler finding does not blame rank 1 for the 750ms "
              f"sleep: {s['evidence']}", file=sys.stderr)
        return 1, ""

    recompiles = [f for f in findings if f["category"] == "recompile"
                  and "train_step" in f["title"]]
    if not recompiles:
        print("no recompile finding for train_step", file=sys.stderr)
        return 1, ""
    blamed = recompiles[0]["evidence"].get("blamed_arguments") or []
    if "seq_len" not in blamed:
        print(f"recompile finding does not blame seq_len: {blamed}",
              file=sys.stderr)
        return 1, ""

    print(f"doctor-smoke OK: straggler rank "
          f"{s['evidence']['blamed_rank']} "
          f"({s['evidence']['blame_seconds'] * 1e3:.0f}ms blame), "
          f"recompile blamed on {blamed}")
    return 0, ""


def _attempt():
    # Fresh workdir per attempt: a retry must not merge the failed
    # attempt's stale trace shards.
    with tempfile.TemporaryDirectory(prefix="hvd_doctor_smoke_") as td:
        return run_smoke(td)


def main() -> int:
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import smoke_util
    return smoke_util.main_with_retry(_attempt, name="doctor-smoke")


if __name__ == "__main__":
    sys.exit(main())
