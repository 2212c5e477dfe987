"""FSDP / ZeRO-3 parameter sharding (parallel/fsdp.py): just-in-time
block gathers, fused reduce-scatter gradients, shard-domain optimizer.
Reference role: DeepSpeed ZeRO-3 layered on hvd allreduce; here the whole
cycle is explicit XLA collectives inside shard_map."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.parallel.fsdp import (flat_size, fsdp_adamw, fsdp_apply,
                                       fsdp_scan_blocks, fsdp_shard_params,
                                       stack_layer_shards)

N = 8
D = 16


def _mlp_params(rng, key=0):
    k = jax.random.PRNGKey(key)
    k1, k2 = jax.random.split(k)
    return {
        "w1": jax.random.normal(k1, (D, 2 * D), jnp.float32) * 0.3,
        "b1": jnp.zeros((2 * D,), jnp.float32),
        "w2": jax.random.normal(k2, (2 * D, D), jnp.float32) * 0.3,
        "b2": jnp.zeros((D,), jnp.float32),
    }


def _block(p, x):
    h = jax.nn.relu(x @ p["w1"] + p["b1"])
    return x + h @ p["w2"] + p["b2"]


class TestFsdpApply:
    def test_forward_matches_unsharded(self, rng):
        params = _mlp_params(rng)
        x = jnp.asarray(rng.standard_normal((N, 4, D)), jnp.float32)
        shards = fsdp_shard_params(params)

        def body(shard, xs):
            return fsdp_apply(_block, params, shard, xs[0])[None]

        out = hvd.spmd(body, in_specs=(P("hvd"), P("hvd")),
                       out_specs=P("hvd"))(shards, x)
        for i in range(N):
            np.testing.assert_allclose(
                np.asarray(out[i]), np.asarray(_block(params, x[i])),
                rtol=1e-5, atol=1e-5)

    def test_grad_is_dp_mean_resharded(self, rng):
        """g_shard from autodiff == the flat dp-mean gradient's own chunk
        — the reduce-scatter IS the gradient sync."""
        params = _mlp_params(rng)
        x = jnp.asarray(rng.standard_normal((N, 4, D)), jnp.float32)
        shards = fsdp_shard_params(params)
        c = shards.shape[0] // N

        def body(shard, xs):
            def loss(s):
                return jnp.mean(fsdp_apply(_block, params, s, xs[0]) ** 2)
            return jax.grad(loss)(shard)[None]

        g = np.asarray(hvd.spmd(body, in_specs=(P("hvd"), P("hvd")),
                                out_specs=P("hvd"))(shards, x)).ravel()

        def ref_loss(p):
            per = [jnp.mean(_block(p, x[i]) ** 2) for i in range(N)]
            return sum(per) / N                  # dp-mean of local losses

        ref = jax.grad(ref_loss)(params)
        flat_ref = np.concatenate(
            [np.asarray(l).ravel() for l in
             jax.tree_util.tree_leaves(ref)])
        np.testing.assert_allclose(g[:flat_ref.size], flat_ref,
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(g[flat_ref.size:], 0.0, atol=1e-7)

    def test_scan_blocks_matches_sequential(self, rng):
        L = 3
        layers = [_mlp_params(rng, key=i) for i in range(L)]
        stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *layers)
        rows = stack_layer_shards(stacked)
        assert rows.shape[0] == L
        x = jnp.asarray(rng.standard_normal((N, 2, D)), jnp.float32)

        def body(rows, xs):
            return fsdp_scan_blocks(_block, layers[0], rows, xs[0])[None]

        out = hvd.spmd(body, in_specs=(P(None, "hvd"), P("hvd")),
                       out_specs=P("hvd"))(rows, x)

        want = x
        for p in layers:
            want = jnp.stack([_block(p, want[i]) for i in range(N)])
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)


class TestFsdpTp:
    def test_fsdp_composes_with_tensor_parallelism(self, rng):
        """2-D layout: params FSDP-sharded over dp within each tp fiber,
        Megatron-split matmuls inside the gathered block (conjugate g
        operator, NOT bare psum — its transpose under check_vma=False
        would multiply cotangents by TP). Loss and per-fiber grads must
        match the single-device model."""
        from jax import lax

        from horovod_tpu.parallel import make_mesh, psum_fwd_identity_bwd
        from horovod_tpu.parallel.fsdp import (flat_size, fsdp_apply,
                                               fsdp_shard_params)

        DP, TP, F = 4, 2, 16
        W1 = rng.standard_normal((D, F)).astype(np.float32) * 0.3
        W2 = rng.standard_normal((F, D)).astype(np.float32) * 0.3
        x = rng.standard_normal((DP, 4, D)).astype(np.float32)
        W1t = np.stack([W1[:, i * F // TP:(i + 1) * F // TP]
                        for i in range(TP)])
        W2t = np.stack([W2[i * F // TP:(i + 1) * F // TP, :]
                        for i in range(TP)])
        shards = np.stack([np.asarray(fsdp_shard_params(
            {"w1": jnp.asarray(W1t[i]), "w2": jnp.asarray(W2t[i])},
            num_shards=DP)) for i in range(TP)])
        template = {
            "w1": jax.ShapeDtypeStruct((D, F // TP), jnp.float32),
            "w2": jax.ShapeDtypeStruct((F // TP, D), jnp.float32)}
        g_tp = psum_fwd_identity_bwd("tp")

        def block(p, h):
            return h + g_tp(jax.nn.relu(h @ p["w1"]) @ p["w2"])

        def body(shard, xs):
            def loss(s):
                y = fsdp_apply(block, template, s[0], xs[0],
                               axis_name="dp")
                return jnp.mean(y ** 2)
            l, g = jax.value_and_grad(loss)(shard)
            return lax.pmean(l, "dp"), g

        mesh = make_mesh({"dp": DP, "tp": TP})
        fn = jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=(P("tp", "dp"), P("dp")),
            out_specs=(P(), P("tp", "dp")), check_vma=False))
        l, g = fn(jnp.asarray(shards), jnp.asarray(x))

        def ref_loss(W1f, W2f):
            per = [jnp.mean((jnp.asarray(x[i])
                             + jax.nn.relu(jnp.asarray(x[i]) @ W1f)
                             @ W2f) ** 2) for i in range(DP)]
            return sum(per) / DP

        rl, (rW1, rW2) = jax.value_and_grad(ref_loss, argnums=(0, 1))(
            jnp.asarray(W1), jnp.asarray(W2))
        np.testing.assert_allclose(float(l), float(rl), rtol=1e-5)
        g = np.asarray(g)
        for i in range(TP):
            Lloc = flat_size({"w1": W1t[i], "w2": W2t[i]})
            flat = g[i].ravel()[:Lloc]
            want = np.concatenate(
                [np.asarray(rW1)[:, i * F // TP:(i + 1) * F // TP].ravel(),
                 np.asarray(rW2)[i * F // TP:(i + 1) * F // TP, :].ravel()])
            np.testing.assert_allclose(flat, want, rtol=2e-4, atol=1e-6)


class TestFsdpTraining:
    def test_training_matches_plain_dp(self, rng):
        """Full ZeRO-3 loop (shard -> grad -> shard-domain adamw) tracks a
        plain replicated-Adam DP loop step for step."""
        params = _mlp_params(rng)
        X = jnp.asarray(rng.standard_normal((N, 8, D)), jnp.float32)
        y = jnp.asarray(rng.standard_normal((N, 8, D)), jnp.float32)

        shards = fsdp_shard_params(params)
        opt = fsdp_adamw(1e-2)
        opt_state = opt.init(shards)

        def step(shard, mu, nu, stepc, Xs, ys):
            def loss(s):
                pred = fsdp_apply(_block, params, s, Xs[0])
                return jnp.mean((pred - ys[0]) ** 2)
            l, g = jax.value_and_grad(loss)(shard)
            from horovod_tpu.optimizer_sharded import ShardedAdamWState
            upd, st2 = opt.update(
                g, ShardedAdamWState(stepc, mu, nu), shard)
            return (shard + upd, st2.mu, st2.nu, st2.step,
                    jax.lax.pmean(l, "hvd"))

        fn = hvd.spmd(step,
                      in_specs=(P("hvd"), P("hvd"), P("hvd"), P("hvd"),
                                P("hvd"), P("hvd")),
                      out_specs=(P("hvd"), P("hvd"), P("hvd"), P("hvd"),
                                 P()))

        # plain DP reference: replicated params, mean grad over all shards
        ref_p = params
        ref_opt = optax.adam(1e-2)
        ref_state = ref_opt.init(ref_p)

        mu, nu, stepc = opt_state.mu, opt_state.nu, opt_state.step
        losses, ref_losses = [], []
        for _ in range(5):
            shards, mu, nu, stepc, l = fn(shards, mu, nu, stepc, X, y)
            losses.append(float(l))

            def ref_loss(p):
                per = [jnp.mean((_block(p, X[i]) - y[i]) ** 2)
                       for i in range(N)]
                return sum(per) / N
            rl, rg = jax.value_and_grad(ref_loss)(ref_p)
            ref_losses.append(float(rl))
            upd, ref_state = ref_opt.update(rg, ref_state, ref_p)
            ref_p = optax.apply_updates(ref_p, upd)

        np.testing.assert_allclose(losses, ref_losses, rtol=1e-4)
        # final sharded params == final replicated params
        got = np.asarray(shards).ravel()[:flat_size(params)]
        want = np.concatenate([np.asarray(l).ravel() for l in
                               jax.tree_util.tree_leaves(ref_p)])
        np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-5)
        assert losses[-1] < losses[0]

    def test_peak_memory_below_gather_upfront(self, rng):
        """Compiled peak temp memory of the FSDP scan is below a variant
        that gathers ALL layers before running them — the per-block
        gather is the point."""
        L = 6
        layers = [_mlp_params(rng, key=i) for i in range(L)]
        stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *layers)
        rows = stack_layer_shards(stacked)
        x = jnp.asarray(rng.standard_normal((N, 2, D)), jnp.float32)

        def fsdp_body(rows, xs):
            def loss(r):
                return jnp.mean(
                    fsdp_scan_blocks(_block, layers[0], r, xs[0]) ** 2)
            return jax.grad(loss)(rows)

        def upfront_body(rows, xs):
            def loss(r):
                full = jax.lax.all_gather(r, "hvd", axis=1, tiled=True)

                def body(h, row):
                    from horovod_tpu.optimizer_sharded import _unflatten
                    p = _unflatten(row[:flat_size(layers[0])], layers[0])
                    return _block(p, h), None
                out, _ = jax.lax.scan(body, xs[0], full)
                return jnp.mean(out ** 2)
            return jax.grad(loss)(rows)

        def temp_bytes(body):
            fn = hvd.spmd(body, in_specs=(P(None, "hvd"), P("hvd")),
                          out_specs=P(None, "hvd"))
            mem = fn.lower(rows, x).compile().memory_analysis()
            if mem is None:
                pytest.skip("memory analysis unavailable on this backend")
            return mem.temp_size_in_bytes

        assert temp_bytes(fsdp_body) < temp_bytes(upfront_body)
