#!/usr/bin/env python
"""CPU-proxy bench pair for the dp×mp mesh: replicated vs dp1xmp2.

Two legs, each measured replicated (1 process, mp=1) and model-parallel
(2 real processes on a ``dp1xmp2`` mesh, the `make mp-smoke` topology):

* **train**: steps/sec of the ZeRO-3 GPT-2 training program
  (``zero3_apply`` gathers + reduce-scattered grads + shard-domain
  AdamW) — the mp run shards params/optimizer across the 2 ranks.
* **serve**: tokens/sec of ``InferenceEngine`` draining a fixed batch
  of requests — the mp run holds 1/mp of the weights and KV pool per
  rank and decodes through the collective-matmul step.

On CPU the collectives are memcpy, so mp=2 is expected to LOSE
throughput — the lines record the mechanism's overhead honestly
(``proxy: true``) and pin the memory win (``param_bytes_per_rank``).
Each line carries ``mesh`` so ``tools/bench_sentinel.py`` never
compares across meshes.

Usage::

    python tools/mp_bench.py                  # print 4 lines
    python tools/mp_bench.py --out mp_bench.jsonl
"""

import argparse
import json
import os
import socket
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TRAIN_STEPS = 8
SERVE_REQUESTS = 12
NEW_TOKENS = 16

# Both legs as one payload so the 2-proc rendezvous happens once. The
# replicated run executes the same payload with mesh=None (no
# distributed init, world of one local device).
PAYLOAD = textwrap.dedent("""
    import json, os, sys, time
    os.environ.pop("XLA_FLAGS", None)
    mesh_env = {mesh_env!r}
    if mesh_env:
        os.environ["HOROVOD_MESH"] = mesh_env
    import jax
    jax.config.update("jax_platforms", "cpu")
    sys.path.insert(0, {repo!r})
    import numpy as np
    import jax.numpy as jnp
    import horovod_tpu as hvd
    if mesh_env:
        pid, port = int(sys.argv[1]), sys.argv[2]
        hvd.init(coordinator_address=f"127.0.0.1:{{port}}",
                 num_processes=2, process_id=pid)
        mesh2d = hvd.mesh2d()
        n = hvd.mp_size()
    else:
        pid, n, mesh2d = 0, 1, None

    from horovod_tpu.models.gpt2 import GPT2, GPT2Config, loss_fn
    from horovod_tpu.parallel import mp as mpmod
    from horovod_tpu.optimizer_sharded import ShardedAdamWState
    from jax.sharding import Mesh

    cfg = GPT2Config.tiny(dtype=jnp.float32)
    model = GPT2(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.ones((1, 4), jnp.int32))["params"]
    rng = np.random.default_rng(11)
    toks = np.asarray(rng.integers(0, cfg.vocab_size, size=(4, 32)),
                      np.int32)

    if mesh2d is None:
        mesh2d = Mesh(np.asarray(jax.local_devices()[:1]).reshape(1, 1),
                      ("dp", "mp"))

    def block(p, tk):
        return loss_fn(model.apply({{"params": p}}, tk), tk)

    flat = np.asarray(mpmod.zero3_shard_params(params, num_shards=n))
    c = flat.shape[0] // n
    opt = mpmod.zero3_adamw(1e-2)

    def train_body(st, tk):
        shard = st["shard"]
        l, g = jax.value_and_grad(lambda s: mpmod.zero3_apply(
            block, params, s, tk, axis_name="mp"))(shard)
        upd, st2 = opt.update(
            g, ShardedAdamWState(st["step"], st["mu"], st["nu"]), shard)
        return {{"shard": shard + upd, "mu": st2.mu, "nu": st2.nu,
                "step": st2.step, "loss": l}}

    prog = jax.jit(mpmod.wrap_spmd(train_body, mesh2d))
    st = mpmod.mp_stack(lambda r: {{
        "shard": flat[r * c:(r + 1) * c],
        "mu": np.zeros((c,), np.float32),
        "nu": np.zeros((c,), np.float32),
        "step": np.zeros((1,), np.int32)}}, mesh2d)
    tk_g = mpmod.mp_broadcast(toks, mesh2d)
    def one_step(st):
        out = prog({{k: st[k] for k in ("shard", "mu", "nu", "step")}},
                   tk_g)
        return out
    st = one_step(st)                      # compile outside the clock
    jax.block_until_ready(st["loss"])
    t0 = time.perf_counter()
    for _ in range({train_steps}):
        st = one_step(st)
    jax.block_until_ready(st["loss"])
    train_sps = {train_steps} / (time.perf_counter() - t0)

    from horovod_tpu.serving.engine import InferenceEngine
    eng = InferenceEngine(model, params, slots=4, max_len=64,
                          block_size=8, prefix_cache=True, spec_k=2,
                          prefill_chunk=8, name="mp_bench")
    prompts = [list(map(int, rng.integers(0, cfg.vocab_size, size=m)))
               for m in rng.integers(5, 17, size={serve_requests})]
    # one warm drain compiles decode/prefill outside the clock
    eng.submit(prompts[0], max_new_tokens=2); eng.run_until_idle()
    t0 = time.perf_counter()
    reqs = [eng.submit(p, max_new_tokens={new_tokens}) for p in prompts]
    eng.run_until_idle()
    wall = time.perf_counter() - t0
    total = sum(len(r.result()) for r in reqs)
    stats = eng.stats()
    if pid == 0:
        print("RESULT " + json.dumps({{
            "train_steps_per_sec": round(train_sps, 3),
            "serve_tokens_per_sec": round(total / wall, 2),
            "serve_total_tokens": total,
            "mp": stats["mp"],
            "mesh": stats["mesh"] or "dp1xmp1",
            "param_bytes_per_rank": stats["param_bytes_per_rank"],
            "kv_pool_bytes_per_rank": stats.get(
                "kv_pool_bytes_per_rank"),
        }}), flush=True)
    if mesh_env:
        hvd.shutdown()
""")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _run_leg(mesh_env, timeout_s=600.0):
    src = PAYLOAD.format(repo=REPO, mesh_env=mesh_env,
                         train_steps=TRAIN_STEPS,
                         serve_requests=SERVE_REQUESTS,
                         new_tokens=NEW_TOKENS)
    if mesh_env:
        port = _free_port()
        procs = [subprocess.Popen(
            [sys.executable, "-c", src, str(pid), str(port)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
            for pid in range(2)]
        outs = [p.communicate(timeout=timeout_s)[0] for p in procs]
        for p, out in zip(procs, outs):
            if p.returncode != 0:
                raise RuntimeError(f"mp leg failed:\n{out}")
        out = outs[0]
    else:
        r = subprocess.run([sys.executable, "-c", src],
                           capture_output=True, text=True,
                           timeout=timeout_s)
        if r.returncode != 0:
            raise RuntimeError(f"replicated leg failed:\n{r.stdout}\n"
                               f"{r.stderr}")
        out = r.stdout
    for line in out.splitlines():
        if line.startswith("RESULT "):
            return json.loads(line[len("RESULT "):])
    raise RuntimeError(f"no RESULT line:\n{out}")


def _git_rev() -> str:
    try:
        return subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], cwd=REPO,
            capture_output=True, text=True, timeout=10).stdout.strip()
    except Exception:
        return ""


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default=None,
                    help="append the JSON lines to this file")
    args = ap.parse_args()

    rep = _run_leg(None)
    mp2 = _run_leg("dp1xmp2")

    import datetime
    ts = datetime.datetime.now(datetime.timezone.utc).isoformat(
        timespec="seconds")
    git = _git_rev()
    common = {"ts": ts, "git": git, "model": "gpt2-tiny", "proxy": True,
              "note": "CPU proxy (collectives are memcpy): records "
              "mesh overhead + per-rank memory, not speedup"}
    lines = []
    for leg, unit, metric in (
            ("train_steps_per_sec", "steps/sec", "zero3_train_steps_per_sec"),
            ("serve_tokens_per_sec", "tokens/sec", "serve_tokens_per_sec")):
        for res, mesh in ((rep, "dp1xmp1"), (mp2, "dp1xmp2")):
            rec = dict(common)
            rec.update({
                "metric": metric, "value": res[leg], "unit": unit,
                "vs_baseline": round(res[leg] / rep[leg], 3),
                "mesh": mesh, "mp": res["mp"], "world": res["mp"],
                "param_bytes_per_rank": res["param_bytes_per_rank"],
            })
            if metric == "serve_tokens_per_sec":
                rec.update({
                    "requests": SERVE_REQUESTS,
                    "max_len": 64, "block_size": 8, "prefill_chunk": 8,
                    "prefix_cache": True, "spec_k": 2,
                    "kv_pool_bytes_per_rank":
                        res["kv_pool_bytes_per_rank"],
                })
            else:
                rec.update({"steps": TRAIN_STEPS, "batch": 4,
                            "seq_len": 32})
            lines.append(rec)
    for rec in lines:
        print(json.dumps(rec))
    if args.out:
        with open(args.out, "a") as f:
            for rec in lines:
                f.write(json.dumps(rec) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
