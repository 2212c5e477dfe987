"""The hybrid decoder of gated short convolutions and grouped-query attention
with bias-selected routed experts (``models/lfm2.py``, ``ops/short_conv.py``,
the sigmoid rule of ``ops/moe.routed_share``), on the CPU at a small size
with the published kinds of layer: system against the plain reference of the
benchmark on seeded weights, the convolution against XLA's own and against a
NumPy loop, the routing rule piece by piece, the shares of an expert layer
adding up to the uncut layer, and that the softmax rule is left as it was."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from horovod_tpu import tracing
from horovod_tpu.models import lfm2
from horovod_tpu.ops import moe
from horovod_tpu.ops.short_conv import gated_short_conv

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark"))
from reference import lfm2_moe_ref as ref  # noqa: E402

T = 32


def _kw(cfg):
    return dict(layer_types=cfg.layer_types,
                num_dense_layers=cfg.num_dense_layers,
                num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
                eps=cfg.rms_eps, rope_theta=cfg.rope_theta, top_k=cfg.top_k,
                norm_topk=cfg.norm_topk, routed_scale=cfg.routed_scale,
                experts_first=cfg.experts_held[0])


def _setup(**kw):
    """conv + dense, attention + routed, conv + routed; 8 experts of which 2
    held, top-4, a bias that moves choices, T 32, fp32."""
    cfg = lfm2.LFM2Config.tiny(experts_held=(2, 2), top_k=4,
                               routed_scale=1.5, dtype=jnp.float32, **kw)
    model = lfm2.LFM2(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, T), 0,
                                cfg.vocab_size)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    bias = 0.1 * jax.random.normal(jax.random.PRNGKey(2),
                                   (cfg.num_layers, cfg.experts_total))
    return cfg, model, params, tokens, bias


# ---------------------------------------------------------------------------
# system against the reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("attention,remat,policy", [
    ("dense", False, "full"), ("flash", False, "full"),
    ("flash", True, "full"), ("flash", True, "dots")])
def test_loss_and_gradients_match_the_reference(attention, remat, policy):
    cfg, model, params, tokens, bias = _setup(
        attention=attention, remat=remat, remat_policy=policy,
        flash_blocks=(16, 16))
    loss, grads = jax.value_and_grad(
        lambda p: lfm2.loss_fn(model, p, tokens, bias))(params)
    tree = ref.from_system(params, cfg.num_layers)
    want, want_grads = jax.value_and_grad(
        lambda r: ref.loss(r, tokens, bias, **_kw(cfg)))(tree)
    np.testing.assert_allclose(float(loss), float(want), rtol=1e-5)
    got = ref.from_system(grads, cfg.num_layers)
    assert (jax.tree_util.tree_structure(got)
            == jax.tree_util.tree_structure(want_grads))
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree_util.tree_leaves(want_grads)):
        scale = float(jnp.max(jnp.abs(b)))
        assert scale > 0, path
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-5 * scale, err_msg=str(path))


def test_the_bias_moves_the_loss_and_takes_no_gradient():
    cfg, model, params, tokens, bias = _setup()
    with_bias = lfm2.loss_fn(model, params, tokens, bias)
    without = lfm2.loss_fn(model, params, tokens)
    assert abs(float(with_bias) - float(without)) > 1e-6
    # the choice is all the bias enters: it has no gradient of its own
    g = jax.grad(lambda b: lfm2.loss_fn(model, params, tokens, b))(bias)
    assert not np.asarray(g).any()
    # and a row of a dense layer is never read
    moved = bias.at[0].add(10.0)
    assert float(lfm2.loss_fn(model, params, tokens, moved)) == \
        float(with_bias)


def test_reference_loss_and_grad_norm_by_micro_batches():
    cfg, model, params, tokens, bias = _setup()
    tree = ref.from_system(params, cfg.num_layers)
    whole = ref.loss_and_grad_norm(tree, tokens, bias, micro=2, **_kw(cfg))
    rows = ref.loss_and_grad_norm(tree, tokens, bias, micro=1, **_kw(cfg))
    np.testing.assert_allclose(whole, rows, rtol=1e-5)
    low = ref.loss_and_grad_norm(tree, tokens, bias, micro=1,
                                 dtype="bfloat16", **_kw(cfg))
    # another precision gives another number, and not a far one
    assert 1e-6 < abs(low[0] - whole[0]) / whole[0] < 5e-2


def test_the_block_takes_its_operator_and_its_feed_forward_by_layer():
    cfg, model, params, tokens, _ = _setup()
    assert cfg.layer_types == ("conv", "full_attention", "conv")
    kinds = [sorted(k for k in params[f"h{i}"] if not k.startswith("norm"))
             for i in range(cfg.num_layers)]
    assert kinds == [["conv", "mlp"], ["attn", "moe"], ["conv", "moe"]]
    # the same layers told otherwise build another tree
    other = lfm2.LFM2(lfm2.LFM2Config.tiny(
        layer_types=("full_attention", "conv", "conv"), num_dense_layers=2))
    tree = jax.eval_shape(lambda: other.init(jax.random.PRNGKey(0),
                                             tokens))["params"]
    kinds = [sorted(k for k in tree[f"h{i}"] if not k.startswith("norm"))
             for i in range(3)]
    assert kinds == [["attn", "mlp"], ["conv", "mlp"], ["conv", "moe"]]
    assert tree["h1"]["conv"]["taps"].shape == (32, 3)
    assert "lm_head" not in tree                # the head is wte's rows


@pytest.mark.parametrize("changes,match", [
    (dict(layer_types=("conv", "conv")), "names 2 layers"),
    (dict(layer_types=("conv", "window", "conv")), "expected 'conv'"),
    (dict(num_kv_heads=3), "must divide"),
    (dict(use_expert_bias=False), "use_expert_bias=False")])
def test_the_model_refuses_what_it_cannot_build(changes, match):
    model = lfm2.LFM2(lfm2.LFM2Config.tiny(**changes))
    tokens = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(ValueError, match=match):
        model.init(jax.random.PRNGKey(0), tokens, jnp.zeros((3, 8)))


def test_the_published_defaults():
    cfg = lfm2.LFM2Config()
    assert len(cfg.layer_types) == cfg.num_layers == 40
    assert cfg.layer_types.count("full_attention") == 10
    assert cfg.layer_types[:6] == ("conv", "conv", "full_attention", "conv",
                                   "conv", "conv")
    assert (cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.d_ff, cfg.d_expert, cfg.experts_total, cfg.top_k,
            cfg.conv_taps, cfg.num_dense_layers) == (
                2048, 32, 8, 64, 11776, 1536, 64, 4, 3, 2)
    from horovod_tpu import models
    assert models.LFM2 is lfm2.LFM2 and models.LFM2Config is lfm2.LFM2Config


def test_serving_refuses_the_family_and_says_why():
    from horovod_tpu.models import generate as gen
    cfg = lfm2.LFM2Config.tiny()
    with pytest.raises(TypeError, match="trained here and not served.*conv"):
        gen.decode_family(cfg)
    with pytest.raises(TypeError, match="trained here and not served"):
        gen.generate(lfm2.LFM2(cfg), {}, jnp.zeros((1, 4), jnp.int32), 2)


# ---------------------------------------------------------------------------
# the gated short convolution
# ---------------------------------------------------------------------------

def _conv_inputs(t, d=5, k=3, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (2, t, 3 * d), jnp.float32),
            jax.random.normal(ks[1], (d, k), jnp.float32),
            jax.random.normal(ks[2], (2, t, d), jnp.float32))


def _by_lax_conv(bcx, taps):
    """The same layer by XLA's convolution: depthwise (a feature group a
    channel), left-padded by ``K - 1``."""
    d, k = taps.shape
    b, c, x = bcx[..., :d], bcx[..., d:2 * d], bcx[..., 2 * d:]
    conv = jax.lax.conv_general_dilated(
        b * x, taps.T[:, None, :], window_strides=(1,),
        padding=[(k - 1, 0)], dimension_numbers=("NWC", "WIO", "NWC"),
        feature_group_count=d, precision=jax.lax.Precision.HIGHEST)
    return c * conv


@pytest.mark.parametrize("t", [1, 2, 3, 37])
def test_gated_short_conv_is_the_depthwise_left_padded_convolution(t):
    bcx, taps, do = _conv_inputs(t)
    np.testing.assert_allclose(np.asarray(gated_short_conv(bcx, taps)),
                               np.asarray(_by_lax_conv(bcx, taps)),
                               atol=1e-5)
    mine, theirs = (jax.grad(lambda bcx, taps: jnp.sum(do * f(bcx, taps)),
                             argnums=(0, 1))(bcx, taps)
                    for f in (gated_short_conv, _by_lax_conv))
    for a, b in zip(mine, theirs):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


@pytest.mark.parametrize("t", [1, 2, 3, 37])
def test_gated_short_conv_against_a_numpy_loop(t):
    bcx, taps, do = (np.asarray(a, np.float64) for a in _conv_inputs(t))
    n, _, d = do.shape
    k = taps.shape[1]
    b, c, x = bcx[..., :d], bcx[..., d:2 * d], bcx[..., 2 * d:]
    z = b * x
    conv, dz, dtaps = np.zeros_like(z), np.zeros_like(z), np.zeros_like(taps)
    for i in range(n):
        for pos in range(t):
            for j in range(k):
                src = pos - (k - 1) + j
                if src >= 0:
                    conv[i, pos] += taps[:, j] * z[i, src]
    dconv = do * c
    for i in range(n):
        for pos in range(t):
            for j in range(k):
                src = pos - (k - 1) + j
                if src >= 0:
                    dz[i, src] += taps[:, j] * dconv[i, pos]
                    dtaps[:, j] += dconv[i, pos] * z[i, src]
    want_dbcx = np.concatenate([dz * x, do * conv, dz * b], axis=-1)
    f = lambda bcx, taps: jnp.sum(jnp.asarray(do, jnp.float32)
                                  * gated_short_conv(bcx, taps))
    args = (jnp.asarray(bcx, jnp.float32), jnp.asarray(taps, jnp.float32))
    np.testing.assert_allclose(np.asarray(gated_short_conv(*args)), c * conv,
                               atol=1e-5)
    got_dbcx, got_dtaps = jax.grad(f, argnums=(0, 1))(*args)
    np.testing.assert_allclose(np.asarray(got_dbcx), want_dbcx, atol=1e-4)
    np.testing.assert_allclose(np.asarray(got_dtaps), dtaps, atol=1e-4)


def test_gated_short_conv_is_causal_with_a_halo_of_two():
    """Output ``t`` depends on inputs ``t - 2 .. t`` and no others."""
    bcx, taps, _ = _conv_inputs(9)
    jac = jax.jacobian(lambda bcx: gated_short_conv(bcx, taps)[0].sum(-1))(
        bcx)[:, 0]                                  # (t out, t in, 3 d)
    touched = np.asarray(jnp.abs(jac).sum(-1) > 0)
    want = np.array([[0 <= o - i <= 2 for i in range(9)] for o in range(9)])
    np.testing.assert_array_equal(touched, want)


def test_gated_short_conv_keeps_the_dtype_and_refuses_other_shapes():
    bcx, taps, _ = _conv_inputs(8)
    low = gated_short_conv(bcx.astype(jnp.bfloat16), taps)
    assert low.dtype == jnp.bfloat16 and low.shape == (2, 8, 5)
    np.testing.assert_allclose(
        np.asarray(low, np.float32),
        np.asarray(gated_short_conv(
            bcx.astype(jnp.bfloat16).astype(jnp.float32), taps)),
        rtol=1e-2, atol=1e-2)                       # fp32 inside, one rounding
    with pytest.raises(ValueError, match="3 d"):
        gated_short_conv(bcx[..., :14], taps)
    with pytest.raises(ValueError, match="taps"):
        gated_short_conv(bcx, taps[:4])


# ---------------------------------------------------------------------------
# the routing rule
# ---------------------------------------------------------------------------

def _layer(n=64, d=32, f=16, experts=8, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (n, d), jnp.float32)
    router = jax.random.normal(ks[1], (d, experts), jnp.float32) * 0.3
    w_gate = jax.random.normal(ks[2], (experts, d, f), jnp.float32) * 0.2
    w_up = jax.random.normal(ks[3], (experts, d, f), jnp.float32) * 0.2
    w_down = jax.random.normal(ks[4], (experts, f, d), jnp.float32) * 0.2
    bias = jax.random.normal(ks[5], (experts,), jnp.float32) * 0.3
    return x, router, w_gate, w_up, w_down, bias


def _rule_gates(x, router, bias, top_k, eps=1e-6, scale=1.0):
    """The rule written out: sigmoid scores, the top of score + bias, the
    unbiased scores of the chosen over their sum + eps, times scale; as
    dense (n, experts) gates and the chosen sets."""
    s = jax.nn.sigmoid(x @ router)
    _, choice = jax.lax.top_k(s + bias, top_k)
    g = jnp.take_along_axis(s, choice, axis=-1)
    g = scale * g / (g.sum(-1, keepdims=True) + eps)
    dense = jnp.zeros_like(s).at[jnp.arange(x.shape[0])[:, None],
                                 choice].set(g)
    return dense, choice


def _share_gates(x, router, bias, top_k, **rule):
    """The layer's own gates, (n, experts), read off its result: with one
    more input channel that is 1 everywhere (and weighs nothing in the
    router), expert ``e`` held alone with weights that see that channel
    only gives ``silu(1) * gate[p, e]`` in its share's first channel."""
    n, d = x.shape
    experts = router.shape[1]
    x1 = jnp.concatenate([x, jnp.ones((n, 1))], axis=1)
    r1 = jnp.concatenate([router, jnp.zeros((1, experts))], axis=0)
    w_in = jnp.zeros((1, d + 1, 1)).at[0, d, 0].set(1.0)
    w_out = jnp.zeros((1, 1, d + 1)).at[0, 0, 0].set(1.0)
    cols = [moe.routed_share(x1, r1, w_in, w_in, w_out, first=e, top_k=top_k,
                             dtype=jnp.float32, score="sigmoid",
                             select_bias=bias, **rule)[0][:, 0]
            for e in range(experts)]
    return jnp.stack(cols, axis=1) / float(jax.nn.silu(1.0))


def test_a_bias_changes_the_chosen_set_and_not_the_gates():
    x, router, *_, bias = _layer()
    mine = _share_gates(x, router, bias, 2, norm_eps=1e-6)
    want, choice = _rule_gates(x, router, bias, 2)
    np.testing.assert_allclose(np.asarray(mine), np.asarray(want), atol=1e-6)
    # the bias did change choices here ...
    _, plain = jax.lax.top_k(jax.nn.sigmoid(x @ router), 2)
    moved = (np.asarray(choice)[:, :, None]
             != np.asarray(plain)[:, None, :]).all(-1)
    assert 0 < moved.sum() < moved.size
    # ... and where it did, the gate is still the unbiased score's share
    s = np.asarray(jax.nn.sigmoid(x @ router))
    p, j = np.argwhere(moved)[0]
    e = int(choice[p, j])
    chosen = s[p, np.asarray(choice[p])]
    assert float(mine[p, e]) == pytest.approx(
        s[p, e] / (chosen.sum() + 1e-6), rel=1e-5)
    # a gate weighed by the biased score would read otherwise
    biased = (s[p, e] + float(bias[e])) / (
        chosen.sum() + float(bias[np.asarray(choice[p])].sum()))
    assert abs(float(mine[p, e]) - biased) > 1e-4


def test_the_normaliser_carries_its_epsilon():
    x, router, *_, bias = _layer()
    # scores so small that 1e-6 shows: sigmoid(-14) ~ 8e-7
    router = router * 0
    x = x.at[:, 0].set(1.0)
    router = router.at[0].set(-14.0)
    with_eps = _share_gates(x, router, bias, 2, norm_eps=1e-6)
    without = _share_gates(x, router, bias, 2)
    s = float(jax.nn.sigmoid(-14.0))
    assert float(without.sum(1)[0]) == pytest.approx(1.0, rel=1e-5)
    assert float(with_eps.sum(1)[0]) == pytest.approx(
        2 * s / (2 * s + 1e-6), rel=1e-4)
    assert float(with_eps.sum(1)[0]) < 0.7


def test_the_scaling_factor_multiplies_the_gates():
    x, router, *_, bias = _layer()
    one = _share_gates(x, router, bias, 4, norm_eps=1e-6)
    scaled = _share_gates(x, router, bias, 4, norm_eps=1e-6, scale=2.5)
    np.testing.assert_allclose(np.asarray(scaled), 2.5 * np.asarray(one),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(scaled.sum(1)), 2.5, rtol=1e-4)


def test_an_unknown_score_is_refused():
    x, router, w_gate, w_up, w_down, _ = _layer()
    with pytest.raises(ValueError, match="unknown score"):
        moe.routed_share(x, router, w_gate, w_up, w_down, first=0, top_k=2,
                         score="tanh")


def _uncut(x, router, w_gate, w_up, w_down, bias, top_k, scale=1.0):
    """The whole layer by the reference: every expert held."""
    p = {"router": router, "w_gate": w_gate, "w_up": w_up, "w_down": w_down}
    with jax.default_matmul_precision("highest"):
        return ref._experts(x, p, bias, top_k=top_k, norm_topk=True,
                            routed_scale=scale, experts_first=0)[0]


_SIGMOID = dict(score="sigmoid", norm_eps=1e-6, dtype=jnp.float32)


@pytest.mark.parametrize("top_k", [2, 4])
def test_the_four_shares_add_up_to_the_uncut_layer(top_k):
    x, router, w_gate, w_up, w_down, bias = _layer()
    total = jnp.zeros_like(x)
    given = 0
    for first in range(0, 8, 2):
        held = slice(first, first + 2)
        out, aux = moe.routed_share(
            x, router, w_gate[held], w_up[held], w_down[held], first=first,
            top_k=top_k, select_bias=bias, scale=1.5, **_SIGMOID)
        share = _uncut(x, router,
                       *(jnp.where((jnp.arange(8) // 2 == first // 2
                                    )[:, None, None], w, 0)
                         for w in (w_gate, w_up, w_down)), bias, top_k, 1.5)
        np.testing.assert_allclose(np.asarray(out), np.asarray(share),
                                   atol=1e-5)
        total = total + out
        given += int(aux["group_sizes"].sum())
    assert given == x.shape[0] * top_k          # every assignment, once
    np.testing.assert_allclose(
        np.asarray(total),
        np.asarray(_uncut(x, router, w_gate, w_up, w_down, bias, top_k,
                          1.5)), atol=1e-5)


def test_no_assignment_is_dropped_when_the_bias_sends_everyone_to_one():
    x, router, w_gate, w_up, w_down, bias = _layer()
    bias = bias.at[3].set(5.0)                  # everything to expert 3
    out, aux = moe.routed_share(x, router, w_gate[2:4], w_up[2:4],
                                w_down[2:4], first=2, top_k=2,
                                select_bias=bias, **_SIGMOID)
    assert int(aux["group_sizes"][1]) == x.shape[0]
    assert bool((aux["choice"] == 3).any(-1).all())
    want = _uncut(x, router, *(jnp.where((jnp.arange(8) // 2 == 1
                                          )[:, None, None], w, 0)
                               for w in (w_gate, w_up, w_down)), bias, 2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5)


def test_gradients_of_the_share_are_the_references():
    x, router, w_gate, w_up, w_down, bias = _layer(n=32)
    held = slice(4, 6)

    def mine(x, router, w_gate, w_up, w_down):
        out, _ = moe.routed_share(x, router, w_gate, w_up, w_down, first=4,
                                  top_k=4, select_bias=bias, **_SIGMOID)
        return jnp.sum(out * jnp.cos(out))

    def theirs(x, router, w_gate, w_up, w_down):
        p = {"router": router, "w_gate": w_gate, "w_up": w_up,
             "w_down": w_down}
        with jax.default_matmul_precision("highest"):
            out = ref._experts(x, p, bias, top_k=4, norm_topk=True,
                               routed_scale=1.0, experts_first=4)[0]
        return jnp.sum(out * jnp.cos(out))

    args = (x, router, w_gate[held], w_up[held], w_down[held])
    got = jax.grad(mine, argnums=range(5))(*args)
    want = jax.grad(theirs, argnums=range(5))(*args)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


def test_under_an_ep_axis_the_shares_are_exchanged_under_the_sigmoid_rule():
    """Four peers, positions and experts sharded over ``ep``: every peer
    ends with the whole layer's result for its own positions."""
    x, router, w_gate, w_up, w_down, bias = _layer()
    layer = moe.RoutedExperts(8, (0, 2), 2, 16, dtype=jnp.float32,
                              ep_axis="ep", score="sigmoid", norm_eps=1e-6,
                              scale=1.5)
    mesh = Mesh(np.array(jax.devices()[:4]), ("ep",))

    def run(x, router, w_gate, w_up, w_down, bias):
        params = {"router": router, "w_gate": w_gate, "w_up": w_up,
                  "w_down": w_down}
        return layer.apply({"params": params}, x[None], bias)[0]

    out = jax.jit(jax.shard_map(
        run, mesh=mesh,
        in_specs=(P("ep"), P(), P("ep"), P("ep"), P("ep"), P()),
        out_specs=P("ep")))(x, router, w_gate, w_up, w_down, bias)
    np.testing.assert_allclose(
        np.asarray(out),
        np.asarray(_uncut(x, router, w_gate, w_up, w_down, bias, 2, 1.5)),
        atol=1e-5)


def test_the_softmax_rule_traces_as_it_did():
    """The routing rule's arguments at their defaults add nothing: the
    default call traces to the jaxpr of the softmax rule named outright,
    which holds no sigmoid, no bias and no epsilon."""
    x, router, w_gate, w_up, w_down, bias = _layer()
    args = (x, router, w_gate[:2], w_up[:2], w_down[:2])
    trace = lambda **kw: str(jax.make_jaxpr(
        lambda *a: moe.routed_share(*a, first=0, top_k=2, **kw)[0])(*args))
    default = trace()
    assert default == trace(score="softmax", select_bias=None, norm_eps=0.0,
                            scale=1.0)
    # one logistic is the experts' silu; the scores are a softmax's exp
    assert default.count(" logistic ") == 1 and " exp " in default
    sigmoid = trace(score="sigmoid", select_bias=bias, norm_eps=1e-6,
                    scale=2.0)
    assert sigmoid.count(" logistic ") == 2 and " exp " not in sigmoid
    # and through the layer: RoutedExperts as the block-diffusion decoder
    # builds it holds the softmax rule
    layer = moe.RoutedExperts(8, (0, 2), 2, 16)
    params = layer.init(jax.random.PRNGKey(0), x[None])["params"]
    text = str(jax.make_jaxpr(
        lambda p: layer.apply({"params": p}, x[None]))(params))
    assert text.count(" logistic ") == 1 and " exp " in text


# ---------------------------------------------------------------------------
# names and gauges
# ---------------------------------------------------------------------------

def _gauge(name, program):
    import horovod_tpu as hvd
    return [s["value"] for s in hvd.metrics.snapshot()["gauges"].get(name, ())
            if s["labels"].get("program") == program]


def test_the_routing_manifest_of_a_traced_step():
    from horovod_tpu.ops.flash_attention import causal_tiles
    cfg, model, params, tokens, bias = _setup(attention="flash",
                                              flash_blocks=(16, 16))
    with tracing.program("lfm2_step"):
        jax.make_jaxpr(lambda p: lfm2.loss_fn(model, p, tokens, bias))(
            params)
    visited, total = causal_tiles(T, 16, 16)
    want = {"moe_rows_bound": 2 * T * 2, "moe_rows_tight": 2 * T * 2,
            "causal_tiles_visited": visited, "causal_tiles_total": total}
    for name, value in want.items():
        assert _gauge(name, "lfm2_step") == [value], name
    assert visited < total


def test_the_share_of_choices_the_bias_moved():
    tracing.routing_bias_moved("lfm2_look", [10, 30], 200)
    assert _gauge("moe_bias_moved_share", "lfm2_look") == [
        pytest.approx(0.1)]
    tracing.routing_bias_moved("lfm2_look", [0, 0], 200)
    assert _gauge("moe_bias_moved_share", "lfm2_look") == [0.0]


def test_routing_load_from_the_auxiliary_output():
    cfg, model, params, tokens, bias = _setup()
    _, kept = model.apply({"params": params}, tokens, bias,
                          mutable=["intermediates"])
    assert sorted(kept["intermediates"]) == ["h1", "h2"]    # routed layers
    choice = np.asarray(kept["intermediates"]["h1"]["moe"]["choice"][0])
    sizes = np.asarray(kept["intermediates"]["h1"]["moe"]["group_sizes"][0])
    assert sizes.tolist() == [int((choice == e).sum()) for e in (2, 3)]
