"""Mixture-of-Experts routing / expert-parallel layer (SURVEY §2 row 26 —
ep joins dp/tp/sp/pp as a first-class mesh axis)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.ops.moe import MoEMLP, Top1Router, switch_load_balance_loss


def test_router_dispatch_is_one_hot_and_capacity_bounded(rng):
    n, d, e = 32, 8, 4
    x = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
    router = Top1Router(num_experts=e, capacity_factor=1.0)
    params = router.init(jax.random.PRNGKey(0), x)
    dispatch, combine, aux = router.apply(params, x)
    c = dispatch.shape[-1]
    assert dispatch.shape == (n, e, c) and c == n // e

    d_np = np.asarray(dispatch)
    # Each token occupies at most one (expert, slot) pair.
    assert np.all(d_np.reshape(n, -1).sum(-1) <= 1.0 + 1e-6)
    # Each (expert, slot) holds at most one token.
    assert np.all(d_np.reshape(n, -1).sum(0) <= 1.0 + 1e-6)
    # Combine weights equal the router prob on dispatched slots.
    comb = np.asarray(combine)
    assert np.all(comb[d_np > 0] > 0)
    assert float(aux) >= 1.0 - 1e-3  # E * sum f*p is minimised at 1


def test_load_balance_loss_uniform_is_one():
    n, e = 64, 8
    probs = jnp.full((n, e), 1.0 / e)
    idx = jnp.asarray(np.arange(n) % e, jnp.int32)
    assert abs(float(switch_load_balance_loss(probs, idx)) - 1.0) < 1e-5


def test_moe_identical_experts_matches_gated_dense(rng):
    # With every expert holding the same weights and ample capacity, the MoE
    # output equals gate_prob * dense_mlp(x) token-wise.
    b, t, d, f, e = 2, 8, 8, 16, 4
    x = jnp.asarray(rng.standard_normal((b, t, d)), jnp.float32)
    layer = MoEMLP(num_experts=e, d_ff=f, capacity_factor=float(e),
                   dtype=jnp.float32)
    params = layer.init(jax.random.PRNGKey(0), x)["params"]

    params["w_in"] = jnp.broadcast_to(params["w_in"][:1],
                                      params["w_in"].shape)
    params["w_out"] = jnp.broadcast_to(params["w_out"][:1],
                                       params["w_out"].shape)

    out, aux = layer.apply({"params": params}, x)

    tokens = x.reshape(-1, d)
    logits = tokens @ np.asarray(params["router"]["router"])
    gate = jax.nn.softmax(logits, axis=-1).max(axis=-1)
    h = jax.nn.gelu(tokens @ params["w_in"][0] + params["b_in"][0])
    dense = h @ params["w_out"][0] + params["b_out"][0]
    expected = (gate[:, None] * dense).reshape(b, t, d)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=1e-4, atol=1e-4)


def test_moe_gradients_flow_to_all_params(rng):
    b, t, d, f, e = 2, 8, 8, 16, 4
    x = jnp.asarray(rng.standard_normal((b, t, d)), jnp.float32)
    layer = MoEMLP(num_experts=e, d_ff=f, dtype=jnp.float32)
    params = layer.init(jax.random.PRNGKey(0), x)["params"]

    def loss(p):
        out, aux = layer.apply({"params": p}, x)
        return jnp.mean(out ** 2) + 1e-2 * aux

    grads = jax.grad(loss)(params)
    for path, g in jax.tree_util.tree_leaves_with_path(grads):
        assert float(jnp.max(jnp.abs(g))) > 0, path


def test_moe_sharded_over_ep_matches_single_device(rng):
    b, t, d, f, e = 2, 16, 8, 16, 4
    x = jnp.asarray(rng.standard_normal((b, t, d)), jnp.float32)
    layer = MoEMLP(num_experts=e, d_ff=f, capacity_factor=2.0,
                   dtype=jnp.float32)
    params = layer.init(jax.random.PRNGKey(0), x)["params"]
    ref, ref_aux = layer.apply({"params": params}, x)

    from horovod_tpu.parallel import make_mesh
    mesh = make_mesh({"dp": 2, "ep": 4})
    ep_sharded = {
        "router": {"router": NamedSharding(mesh, P())},
        "w_in": NamedSharding(mesh, P("ep")),
        "b_in": NamedSharding(mesh, P("ep")),
        "w_out": NamedSharding(mesh, P("ep")),
        "b_out": NamedSharding(mesh, P("ep")),
    }
    params_s = jax.tree_util.tree_map(
        lambda p, s: jax.device_put(p, s), params, ep_sharded,
        is_leaf=lambda v: isinstance(v, jnp.ndarray))
    x_s = jax.device_put(x, NamedSharding(mesh, P("dp")))

    out, aux = jax.jit(lambda p, x: layer.apply({"params": p}, x))(params_s,
                                                                   x_s)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(float(aux), float(ref_aux), rtol=1e-5)


def test_gpt2_moe_trains(rng):
    from horovod_tpu.models.gpt2 import GPT2, GPT2Config, loss_fn_moe
    import optax
    cfg = GPT2Config.tiny(dtype=jnp.float32, num_experts=4)
    model = GPT2(cfg)
    tokens = jnp.asarray(rng.integers(0, 256, (2, 32)), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    assert "moe" in params["h0"]["mlp"], list(params["h0"]["mlp"])

    opt = optax.adam(1e-2)
    state = opt.init(params)

    @jax.jit
    def step(params, state):
        l, g = jax.value_and_grad(
            lambda p: loss_fn_moe(model, p, tokens))(params)
        u, state2 = opt.update(g, state, params)
        return optax.apply_updates(params, u), state2, l

    losses = []
    for _ in range(10):
        params, state, l = step(params, state)
        losses.append(float(l))
    assert losses[-1] < losses[0], losses


class TestTop2Router:
    """GShard top-2 routing: two experts per token, renormalized gates,
    top-1 slots assigned before top-2 under capacity pressure."""

    def _route(self, n=32, e=4, d=8, cf=2.0, seed=0):
        from horovod_tpu.ops.moe import Top2Router
        rng = np.random.default_rng(seed)
        x = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
        r = Top2Router(e, cf)
        v = r.init(jax.random.PRNGKey(0), x)
        return r.apply(v, x)

    def test_two_assignments_and_normalized_gates(self):
        dispatch, combine, aux = self._route()
        dispatch = np.asarray(dispatch)
        combine = np.asarray(combine)
        per_token = dispatch.sum(axis=(1, 2))
        assert ((per_token > 0) & (per_token <= 2)).all()
        # Un-dropped tokens' combine weights sum to ~1 (renormalized pair).
        full = per_token == 2
        np.testing.assert_allclose(combine.sum(axis=(1, 2))[full], 1.0,
                                   rtol=1e-5)
        # each (expert, slot) holds at most one token
        assert (dispatch.sum(axis=0) <= 1.0 + 1e-6).all()
        assert float(aux) > 0

    def test_capacity_drops_second_choices_first(self):
        # Tiny capacity: top-1 queue fills first, so every expert's slots
        # are dominated by first choices.
        dispatch, combine, aux = self._route(n=64, e=2, cf=0.25)
        dispatch = np.asarray(dispatch)
        assert dispatch.sum() > 0
        assert (dispatch.sum(axis=0) <= 1.0 + 1e-6).all()

    def test_moemlp_top2_trains(self):
        from horovod_tpu.ops.moe import MoEMLP
        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.standard_normal((2, 16, 8)), jnp.float32)
        m = MoEMLP(4, 16, router_type="top2", dtype=jnp.float32)
        v = m.init(jax.random.PRNGKey(0), x)

        def loss(params):
            out, aux = m.apply(params, x)
            return jnp.mean(out ** 2) + 1e-2 * aux

        l, g = jax.value_and_grad(loss)(v)
        assert np.isfinite(float(l))
        assert all(np.isfinite(np.asarray(t)).all()
                   for t in jax.tree_util.tree_leaves(g))

    def test_unknown_router_raises(self):
        from horovod_tpu.ops.moe import MoEMLP
        x = jnp.zeros((1, 4, 8))
        m = MoEMLP(2, 8, router_type="topk")
        with pytest.raises(ValueError, match="router_type"):
            m.init(jax.random.PRNGKey(0), x)


# --- the dropless layer: a route made elsewhere, and ReGLU -----------------

def _dropless(rng, n=24, d=16, f=8, experts=8):
    """Tokens, a second stream, and the weights of ``experts`` experts."""
    draw = lambda *shape: jnp.asarray(rng.standard_normal(shape),
                                      jnp.float32)
    return (draw(n, d), draw(n, d), draw(d, experts),
            draw(experts, d, f) / 4, draw(experts, d, f) / 4,
            draw(experts, f, d) / 4)


def _dense_layer(tokens, read, router, w_gate, w_up, w_down, top_k, act,
                 held=None):
    """The whole layer (or the experts ``held``) in plain jnp: every
    position through every expert, weighted by its gate or zero."""
    probs = jax.nn.softmax(read @ router, axis=-1)
    gate, choice = jax.lax.top_k(probs, top_k)
    gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
    out = jnp.zeros_like(tokens)
    for e in (held or range(router.shape[1])):
        w = jnp.sum(jnp.where(choice == e, gate, 0.0), axis=-1)
        y = (act(tokens @ w_gate[e]) * (tokens @ w_up[e])) @ w_down[e]
        out = out + w[:, None] * y
    return out


@pytest.mark.parametrize("act", ["relu", "silu"])
def test_routed_share_routes_from_a_second_stream(rng, act):
    """``route_from``: the choice and the gates come from the second stream
    and the rows from the tokens, for either activation; every gradient is
    the dense layer's, so the router's goes to the second stream and only
    the rows' to the tokens."""
    from horovod_tpu.ops import moe
    tokens, read, router, w_gate, w_up, w_down = _dropless(rng)
    fn = {"relu": jax.nn.relu, "silu": jax.nn.silu}[act]
    first, held = 2, 4
    target = jnp.asarray(rng.standard_normal(tokens.shape), jnp.float32)

    def share(tokens, read, router, w_gate, w_up, w_down):
        out, aux = moe.routed_share(
            tokens, router, w_gate[first:first + held],
            w_up[first:first + held], w_down[first:first + held],
            first=first, top_k=3, dtype=jnp.float32, route_from=read,
            act=act)
        return jnp.sum(out * target), aux

    def dense(tokens, read, router, w_gate, w_up, w_down):
        return jnp.sum(_dense_layer(
            tokens, read, router, w_gate, w_up, w_down, 3, fn,
            held=range(first, first + held)) * target)

    args = (tokens, read, router, w_gate, w_up, w_down)
    (got, aux), grads = jax.value_and_grad(share, range(6), has_aux=True)(
        *args)
    want, wanted = jax.value_and_grad(dense, range(6))(*args)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)
    for a, b in zip(grads, wanted):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=2e-5)
    assert float(jnp.abs(grads[1]).max()) > 0       # the second stream's
    # the choices are the second stream's, not the tokens'
    probs = jax.nn.softmax(read @ router, axis=-1)
    np.testing.assert_array_equal(np.asarray(aux["choice"]),
                                  np.asarray(jax.lax.top_k(probs, 3)[1]))
    mine = jax.nn.softmax(tokens @ router, axis=-1)
    assert (np.asarray(jax.lax.top_k(mine, 3)[1])
            != np.asarray(aux["choice"])).any()


def test_the_dropless_defaults_trace_as_they_did(rng):
    """``route_from=None`` and ``act="silu"`` add nothing: the default
    call's jaxpr, forward and gradient, is that of the call that names
    them, holds a logistic (the experts' silu) and no relu (the parent's jaxpr, compared once against a checkout of it:
    CHANGES.md); and a second stream equal to the tokens gives the same
    numbers."""
    from horovod_tpu.ops import moe
    tokens, read, router, w_gate, w_up, w_down = _dropless(rng)
    args = (tokens, router, w_gate[:4], w_up[:4], w_down[:4])
    fn = lambda **kw: (lambda *a: jnp.sum(moe.routed_share(
        *a, first=0, top_k=3, **kw)[0].astype(jnp.float32)))
    for trace in (lambda f: str(jax.make_jaxpr(f)(*args)),
                  lambda f: str(jax.make_jaxpr(jax.grad(f, range(5)))(
                      *args))):
        default = trace(fn())
        assert default == trace(fn(route_from=None, act="silu"))
        assert " logistic " in default and "name=relu" not in default
        relu = trace(fn(act="relu"))
        assert " logistic " not in relu and "name=relu" in relu
    same = jax.grad(fn(route_from=tokens, dtype=jnp.float32), (0, 1))(*args)
    plain = jax.grad(fn(dtype=jnp.float32), (0, 1))(*args)
    # the tokens' gradient is then the rows' part alone, the router's whole
    np.testing.assert_allclose(np.asarray(same[1]), np.asarray(plain[1]),
                               rtol=1e-5, atol=1e-6)
    assert np.abs(np.asarray(same[0]) - np.asarray(plain[0])).max() > 1e-4
    with pytest.raises(ValueError, match="unknown act"):
        fn(act="gelu")(*args)
    with pytest.raises(ValueError, match="is not the tokens'"):
        fn(route_from=read[:5])(*args)


def test_routed_experts_over_ep_gathers_the_second_stream(rng):
    """``RoutedExperts(act="relu")`` fed ``route_from`` under an ``ep`` axis
    of 4: each peer's positions and their second stream are gathered, the
    shares reduce-scattered; the result and the gradient of both streams
    are the uncut dense layer's."""
    from jax.sharding import Mesh
    from horovod_tpu.ops import moe
    tokens, read, router, w_gate, w_up, w_down = _dropless(rng, n=32)
    mesh = Mesh(np.array(jax.devices()[:4]), ("ep",))
    layer = moe.RoutedExperts(8, (0, 2), 3, 8, dtype=jnp.float32,
                              ep_axis="ep", act="relu")

    def run(x, r, router, w_gate, w_up, w_down):
        params = {"router": router, "w_gate": w_gate, "w_up": w_up,
                  "w_down": w_down}
        return layer.apply({"params": params}, x[None],
                           route_from=r[None])[0]

    sharded = jax.shard_map(
        run, mesh=mesh,
        in_specs=(P("ep"), P("ep"), P(), P("ep"), P("ep"), P("ep")),
        out_specs=P("ep"))
    weights = (router, w_gate, w_up, w_down)
    target = jnp.asarray(rng.standard_normal(tokens.shape), jnp.float32)
    got, grads = jax.value_and_grad(
        lambda x, r: jnp.sum(sharded(x, r, *weights) * target), (0, 1))(
            tokens, read)
    want, wanted = jax.value_and_grad(
        lambda x, r: jnp.sum(_dense_layer(x, r, *weights, 3, jax.nn.relu)
                             * target), (0, 1))(tokens, read)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)
    for a, b in zip(grads, wanted):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=2e-5)
