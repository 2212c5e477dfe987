#!/usr/bin/env python
"""Preemption smoke: SIGKILL a rank mid-epoch, recover from the last
sharded manifest, prove the losses never noticed.

Two real elastic runs (``runner.run_elastic``, 2 workers each):

* **golden** — uninterrupted; records the per-step loss curve.
* **faulted** — launched with one hot spare and
  ``HOROVOD_FAULT_PLAN="kill@rank=1,step=5"``: rank 1 SIGKILLs itself at
  step 5 (a preempted TPU-VM says no goodbyes), the launcher tears the
  job down, promotes the spare into the dead rank's slot (world stays
  2), and the relaunched workers restore from the last *published*
  manifest — the spare adopting the dead rank's optimizer shard — and
  train to completion.

Asserts:

* exactly one restart, and the relaunched world kept its size via the
  promoted spare (``spare_promoted.json`` + result world);
* bounded recovery: the restored step is within 2 steps of the kill
  step (per-step async cadence + at most one in-flight save lost);
* loss-curve continuity: every post-restore loss is BIT-IDENTICAL to
  the golden run's loss at the same step (and the pre-kill prefix
  matches too) — deterministic resume, not approximately-resumed;
* ``hvd.doctor()`` on the recovered rank reports the measured recovery
  time as a ranked ``recovery`` finding.

Exit 0 = all checks pass. Wired as tier-1
(``tests/test_checkpoint_sharded.py::TestTwoProcessPreemptSmoke``) and
``make preempt-smoke``. ``--bench-out FILE`` appends a recovery-time
JSON line.
"""

import argparse
import glob
import json
import os
import socket
import sys
import tempfile
import time

import smoke_util

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TOTAL, KILL = 8, 5

# The worker: a tiny deterministic linear-regression step with a
# manually-sharded (ZeRO-1) AdamW — each rank owns one chunk of the
# optimizer state, checkpoints it asynchronously every step, and runs
# the fault plan at every step boundary. One script serves workers AND
# the hot spare (standby_if_spare blocks until promoted).
WORKER = r"""
import json, os, sys, traceback
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import jax.numpy as jnp
import horovod_tpu as hvd
from horovod_tpu import checkpoint_sharded as cs
from horovod_tpu import elastic, faults
from horovod_tpu.optimizer_sharded import (ShardedAdamWState,
                                           _adamw_chunk_update)

sdir = elastic.state_dir()
promo = elastic.standby_if_spare()
if promo is not None:
    with open(os.path.join(sdir, "spare_promoted.json"), "w") as f:
        json.dump(promo, f)

def main():
    hvd.init()
    rank, world = jax.process_index(), jax.process_count()
    restart = elastic.restart_count()
    TOTAL, KILL, D, LR = 8, 5, 24, 5e-2
    L = D + 1
    c = -(-L // world)
    mgr = cs.ShardedCheckpointManager(os.path.join(sdir, "ckpt"),
                                      max_to_keep=4)

    rng = np.random.default_rng(7)
    params = {"b": jnp.zeros((), jnp.float32),
              "w": jnp.asarray(rng.standard_normal(D).astype(np.float32))}

    def data(step):
        r = np.random.default_rng(1000 + step)
        return (jnp.asarray(r.standard_normal((16, D)).astype(np.float32)),
                jnp.asarray(r.standard_normal((16,)).astype(np.float32)))

    def loss_fn(p, x, y):
        return jnp.mean(jnp.square(x @ p["w"] + p["b"] - y))

    val_grad = jax.jit(jax.value_and_grad(loss_fn))
    update = jax.jit(lambda g, s, p: _adamw_chunk_update(
        g, s, p, LR, 0.9, 0.999, 1e-8, 0.0))

    def flatten(tree):
        return jnp.concatenate([jnp.ravel(l)
                                for l in jax.tree_util.tree_leaves(tree)])

    def unflatten(flat, tree):
        ls, td = jax.tree_util.tree_flatten(tree)
        out, off = [], 0
        for l in ls:
            n = int(np.prod(l.shape)) if l.shape else 1
            out.append(flat[off:off + n].reshape(l.shape))
            off += n
        return jax.tree_util.tree_unflatten(td, out)

    state = ShardedAdamWState(step=jnp.zeros((1,), jnp.int32),
                              mu=jnp.zeros((c,), jnp.float32),
                              nu=jnp.zeros((c,), jnp.float32))
    start, restored_step = 0, None
    if mgr.latest_step() is not None:
        r = mgr.restore(num_shards=world)   # records recovery seconds
        params = cs._unflatten_like({"params": params},
                                    r.replicated)["params"]
        state = ShardedAdamWState(
            step=jnp.asarray(r.shards["['step']"][rank:rank + 1],
                             jnp.int32),
            mu=jnp.asarray(r.shards["['mu']"][rank]),
            nu=jnp.asarray(r.shards["['nu']"][rank]))
        start = restored_step = r.step
        assert r.meta["cursor"] == r.step   # data stream resumes in place

    losses = {}
    losses_path = os.path.join(sdir, f"losses.{restart}.json")
    for s in range(start + 1, TOTAL + 1):
        faults.fault_point(s)
        x, y = data(s)
        loss, g = val_grad(params, x, y)
        flat_g = flatten(g)
        # Eager allreduce: row r is rank r's contribution (the dead-peer
        # hang on this collective is what makes teardown+relaunch real).
        red = hvd.allreduce(
            jnp.broadcast_to(flat_g, (world, L)), op=hvd.Average)
        flat_g = jnp.asarray(np.asarray(red[rank]))
        flat_g = jnp.pad(flat_g, (0, world * c - L))
        g_chunk = jax.lax.dynamic_slice(flat_g, (rank * c,), (c,))
        p_chunk = jax.lax.dynamic_slice(
            jnp.pad(flatten(params), (0, world * c - L)), (rank * c,), (c,))
        upd_chunk, (stp, mu, nu) = update(g_chunk, state, p_chunk)
        state = ShardedAdamWState(stp, mu, nu)
        # Gather the owned chunks: every rank contributes its chunk
        # scattered at its offset; the sum is the full update vector.
        scatter = np.zeros((world, world * c), np.float32)
        scatter[:, rank * c:(rank + 1) * c] = np.asarray(upd_chunk)
        full_upd = jnp.asarray(
            np.asarray(hvd.allreduce(scatter, op=hvd.Sum)[rank]))[:L]
        params = unflatten(flatten(params) + full_upd, params)
        losses[s] = float(loss)
        if rank == 0:
            with open(losses_path + ".tmp", "w") as f:
                json.dump(losses, f)
            os.replace(losses_path + ".tmp", losses_path)
        # Async sharded save: this rank's shard row only.
        step_f = np.zeros((world,), np.int32)
        step_f[rank] = int(np.asarray(stp)[0])
        mu_f = np.zeros((world, c), np.float32)
        mu_f[rank] = np.asarray(mu)
        nu_f = np.zeros((world, c), np.float32)
        nu_f[rank] = np.asarray(nu)
        mgr.save(s, shards={"step": step_f, "mu": mu_f, "nu": nu_f},
                 replicated={"params": params},
                 meta={"step": s, "cursor": s},
                 unpadded={"['mu']": L, "['nu']": L}, owned=[rank])
    mgr.wait()

    if rank == 0:
        snap = hvd.metrics()

        def gauge(name):
            for g in snap["gauges"].get(name, []):
                return g["value"]
            return None

        rep = hvd.doctor()
        recovery = [f for f in rep["findings"]
                    if f["category"] == "recovery"]
        result = {"world": world, "restart": restart,
                  "restored_step": restored_step,
                  "final_step": int(np.asarray(state.step)[0]),
                  "losses": losses,
                  "recovery_seconds": gauge("elastic_recovery_seconds"),
                  "doctor_recovery": recovery[0] if recovery else None}
        with open(os.path.join(sdir, "result.json"), "w") as f:
            json.dump(result, f)
    mgr.close()
    hvd.shutdown()
    print(f"proc rank={rank} restart={restart} PREEMPT-STEP-OK",
          flush=True)

try:
    main()
except BaseException:
    rk = os.environ.get("HVD_TPU_PROCESS_ID", "spare")
    rs = os.environ.get("HVD_TPU_ELASTIC_RESTART", "0")
    with open(os.path.join(sdir, f"err.{rk}.{rs}.txt"), "w") as f:
        f.write(traceback.format_exc())
    raise
"""


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _collect_errors(sdir: str) -> str:
    out = []
    for p in sorted(glob.glob(os.path.join(sdir, "err.*.txt"))):
        with open(p) as f:
            out.append(f"--- {os.path.basename(p)} ---\n" + f.read())
    return "\n".join(out)


def _fail(msg: str, *dirs: str):
    text = "\n".join(_collect_errors(d) for d in dirs)
    print(f"preempt-smoke FAILED: {msg}\n{text}", file=sys.stderr)
    return 1, msg + "\n" + text


def run_smoke(bench_out=None, timeout_s: float = 240.0):
    """One attempt: (rc, failure_text) for smoke_util's flake retry."""
    sys.path.insert(0, REPO)
    from horovod_tpu.runner.launcher import run_elastic
    env = smoke_util.jit_cache_env(
        {"PYTHONPATH": REPO,
         "XLA_FLAGS": "--xla_force_host_platform_device_count=1"})
    cmd = [sys.executable, "-c", WORKER]
    with tempfile.TemporaryDirectory(prefix="hvd_preempt_") as work:
        golden_dir = os.path.join(work, "golden")
        fault_dir = os.path.join(work, "fault")
        os.makedirs(golden_dir)
        os.makedirs(fault_dir)
        try:
            restarts = run_elastic(cmd, np=2, coordinator_port=_free_port(),
                                   state_dir=golden_dir, extra_env=env,
                                   timeout=timeout_s)
        except Exception as e:
            return _fail(f"golden run: {e}", golden_dir)
        if restarts != 0:
            return _fail(f"golden run restarted {restarts}x", golden_dir)
        with open(os.path.join(golden_dir, "result.json")) as f:
            golden = json.load(f)

        t0 = time.time()
        try:
            restarts = run_elastic(
                cmd, np=2, spares=1, coordinator_port=_free_port(),
                state_dir=fault_dir,
                extra_env={**env,
                           "HOROVOD_FAULT_PLAN": f"kill@rank=1,step={KILL}"},
                timeout=timeout_s)
        except Exception as e:
            return _fail(f"faulted run: {e}", fault_dir)
        wall = time.time() - t0
        if restarts != 1:
            return _fail(f"faulted run restarted {restarts}x (expected 1)",
                         fault_dir)
        with open(os.path.join(fault_dir, "result.json")) as f:
            result = json.load(f)
        # The kill actually happened where planned: attempt 0's loss file
        # stops right before the kill step.
        with open(os.path.join(fault_dir, "losses.0.json")) as f:
            pre = {int(k): v for k, v in json.load(f).items()}
        if max(pre) != KILL - 1:
            return _fail(f"attempt 0 recorded steps {sorted(pre)}; "
                         f"expected to stop at {KILL - 1}", fault_dir)
        # Hot spare kept the world size and was really promoted.
        if result["world"] != 2:
            return _fail(f"relaunched world {result['world']} != 2 — "
                         "spare not promoted", fault_dir)
        if not os.path.exists(os.path.join(fault_dir,
                                           "spare_promoted.json")):
            return _fail("spare_promoted.json missing", fault_dir)
        # Bounded recovery: per-step cadence, at most one in-flight save
        # lost to the SIGKILL.
        restored = result["restored_step"]
        if restored is None or restored < KILL - 2:
            return _fail(f"restored step {restored} < {KILL - 2} — lost "
                         "more than the async in-flight window", fault_dir)
        if result["final_step"] != TOTAL:
            return _fail(f"final step {result['final_step']} != {TOTAL}",
                         fault_dir)
        # Deterministic resume: pre-kill prefix AND post-restore suffix
        # bit-match the uninterrupted run.
        gl = {int(k): v for k, v in golden["losses"].items()}
        post = {int(k): v for k, v in result["losses"].items()}
        for s, v in pre.items():
            if gl[s] != v:
                return _fail(f"pre-kill loss diverged at step {s}: "
                             f"{v} != {gl[s]}", fault_dir)
        if sorted(post) != list(range(restored + 1, TOTAL + 1)):
            return _fail(f"resumed steps {sorted(post)} != "
                         f"{restored + 1}..{TOTAL}", fault_dir)
        for s, v in post.items():
            if gl[s] != v:
                return _fail(f"post-restore loss diverged at step {s}: "
                             f"{v} != {gl[s]} — resume is not "
                             "deterministic", fault_dir)
        # The doctor reported the measured recovery as a ranked finding.
        if result["recovery_seconds"] is None or \
                result["recovery_seconds"] <= 0:
            return _fail("elastic_recovery_seconds not recorded",
                         fault_dir)
        if not result["doctor_recovery"]:
            return _fail("hvd.doctor() has no 'recovery' finding",
                         fault_dir)
        print(f"preempt-smoke OK recovery={result['recovery_seconds']:.2f}s "
              f"restored_step={restored} kill_step={KILL} "
              f"doctor_rank=#{result['doctor_recovery']['rank']} "
              f"wall={wall:.1f}s")
        if bench_out:
            line = {"kind": "preempt_smoke", "np": 2, "spares": 1,
                    "kill_step": KILL, "total_steps": TOTAL,
                    "restored_step": restored,
                    "recovery_seconds": round(
                        result["recovery_seconds"], 3),
                    "faulted_wall_seconds": round(wall, 1),
                    "deterministic_resume": True,
                    "ts": int(time.time())}
            with open(bench_out, "a") as f:
                f.write(json.dumps(line) + "\n")
        return 0, ""


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--bench-out", default=None,
                    help="append a recovery-time JSON line here")
    args = ap.parse_args()
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    return smoke_util.main_with_retry(
        lambda: run_smoke(bench_out=args.bench_out), name="preempt-smoke")


if __name__ == "__main__":
    sys.exit(main())
