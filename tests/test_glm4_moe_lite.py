"""The decoder with latent attention, a shared expert beside the routed ones
and a multi-token-prediction module in its loss (``models/glm4_moe_lite.py``,
``ops/moe.SharedExpert``), on the CPU at a small size with the published
kinds of layer: system against the plain reference of the benchmark on
seeded weights (both loss terms, every gradient leaf), latent attention
against a per-head NumPy loop, the one rotated key that all heads share, the
scale, the two inner norms, the routing rule with its ``1e-20`` and its 1.8,
the shares of an expert layer and the shared expert **once** adding up to
the uncut layer, and what the module predicts from what."""

import dataclasses
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from horovod_tpu import tracing
from horovod_tpu.models import glm4_moe_lite as glm
from horovod_tpu.ops import attention as attention_ops
from horovod_tpu.ops import moe

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark"))
from reference import glm4_moe_lite_ref as ref  # noqa: E402

T = 32


def _kw(cfg, **extra):
    return dict(num_dense_layers=cfg.num_dense_layers,
                num_heads=cfg.num_heads,
                qk_nope_head_dim=cfg.qk_nope_head_dim, eps=cfg.rms_eps,
                rope_theta=cfg.rope_theta, top_k=cfg.top_k,
                norm_topk=cfg.norm_topk, routed_scale=cfg.routed_scale,
                experts_first=cfg.experts_held[0],
                mtp_weight=cfg.mtp_weight, **extra)


def _setup(t=T, **kw):
    """A dense block, two routed + shared ones and the module; 8 experts of
    which 2 held, top-4, a bias that moves choices, fp32."""
    kw.setdefault("dtype", jnp.float32)
    cfg = glm.Glm4MoeLiteConfig.tiny(experts_held=(2, 2), top_k=4, **kw)
    model = glm.Glm4MoeLite(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, t), 0,
                                cfg.vocab_size)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    bias = 0.1 * jax.random.normal(
        jax.random.PRNGKey(2), (cfg.num_layers + 1, cfg.experts_total))
    return cfg, model, params, tokens, bias


# ---------------------------------------------------------------------------
# system against the reference
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _reference_side():
    """The reference's terms, loss and gradients on :func:`_setup`'s
    weights, which no argument of the program's side changes: once."""
    cfg, _, params, tokens, bias = _setup()
    tree = ref.from_system(params, cfg.num_layers)
    terms = jax.jit(lambda r: ref.loss_terms(r, tokens, bias, **_kw(cfg)))
    both = jax.jit(jax.value_and_grad(
        lambda r: ref.loss(r, tokens, bias, **_kw(cfg))))
    return terms(tree), both(tree)


@pytest.mark.parametrize("attention,remat,policy", [
    ("dense", False, "full"), ("flash", False, "full"),
    ("flash", True, "full"), ("flash", True, "dots")])
def test_loss_terms_and_gradients_match_the_reference(attention, remat,
                                                      policy):
    cfg, model, params, tokens, bias = _setup(
        attention=attention, remat=remat, remat_policy=policy,
        flash_blocks=(16, 16))
    (want_main, want_mtp), (want, want_grads) = _reference_side()
    main, mtp = glm.loss_terms(model, params, tokens, bias)
    np.testing.assert_allclose(float(main), float(want_main), rtol=1e-5)
    np.testing.assert_allclose(float(mtp), float(want_mtp), rtol=1e-5)
    assert abs(float(main) - float(mtp)) > 1e-3     # two terms, not one twice
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: glm.loss_fn(model, p, tokens, bias)))(params)
    np.testing.assert_allclose(float(loss), float(want), rtol=1e-5)
    np.testing.assert_allclose(float(loss), float(main) + 0.1 * float(mtp),
                               rtol=1e-6)
    got = ref.from_system(grads, cfg.num_layers)
    assert (jax.tree_util.tree_structure(got)
            == jax.tree_util.tree_structure(want_grads))
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree_util.tree_leaves(want_grads)):
        scale = float(jnp.max(jnp.abs(b)))
        assert scale > 0, path
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-5 * scale, err_msg=str(path))


def test_reference_loss_and_grad_norm_by_micro_batches():
    cfg, model, params, tokens, bias = _setup()
    tree = ref.from_system(params, cfg.num_layers)
    whole = ref.loss_and_grad_norm(tree, tokens, bias, micro=2, **_kw(cfg))
    rows = ref.loss_and_grad_norm(tree, tokens, bias, micro=1, **_kw(cfg))
    np.testing.assert_allclose(whole, rows, rtol=1e-5)
    low = ref.loss_and_grad_norm(tree, tokens, bias, micro=1,
                                 **_kw(cfg, dtype="bfloat16"))
    # another precision gives another number, and not a far one
    assert 1e-6 < abs(low[0] - whole[0]) / whole[0] < 5e-2


@pytest.mark.parametrize("what,change", [
    ("the module's term", dict(mtp_weight=0.0)),
    ("the shared expert", dict(shared=False))])
def test_the_reference_without_a_part_reads_otherwise(what, change):
    """What ``controls_glm4.py`` rests on: a loss without its second term
    and a layer without its shared expert are other numbers."""
    cfg, model, params, tokens, bias = _setup()
    tree = ref.from_system(params, cfg.num_layers)
    whole = ref.loss_and_grad_norm(tree, tokens, bias, **_kw(cfg))
    kw = dict(_kw(cfg), **change)
    less = ref.loss_and_grad_norm(tree, tokens, bias, **kw)
    apart = max(abs(a - b) / b for a, b in zip(less, whole))
    assert apart > 1e-3, what       # the loss, or the gradient's norm
    # and the program's own switches agree with the reference's
    off = (dict(mtp=0) if "mtp_weight" in change
           else dict(shared_experts=0))
    lean = glm.Glm4MoeLite(dataclasses.replace(cfg, **off))
    got = glm.loss_fn(lean, params, tokens,
                      bias[:cfg.num_layers + lean.cfg.mtp])
    np.testing.assert_allclose(float(got), less[0], rtol=1e-5)


# ---------------------------------------------------------------------------
# latent attention
# ---------------------------------------------------------------------------

def _attention_setup(t, **kw):
    cfg = glm.Glm4MoeLiteConfig.tiny(dtype=jnp.float32, **kw)
    layer = glm.LatentAttention(cfg)
    u = jax.random.normal(jax.random.PRNGKey(3), (2, t, cfg.d_model))
    params = dict(layer.init(jax.random.PRNGKey(4), u)["params"])
    # norms away from one, so that a norm left out shows
    for name in ("q_norm", "kv_norm"):
        shape = params[name]["scale"].shape
        params[name] = {"scale": 1.0 + 0.5 * jax.random.normal(
            jax.random.PRNGKey(5), shape)}
    return cfg, layer, params, u


def _rope_np(x, theta):
    """Rotate-half RoPE over (T, D) at positions 0 .. T - 1, float64."""
    d2 = x.shape[-1] // 2
    ang = np.arange(x.shape[0])[:, None] * theta ** (-np.arange(d2) / d2)
    x1, x2 = x[:, :d2], x[:, d2:]
    return np.concatenate([x1 * np.cos(ang) - x2 * np.sin(ang),
                           x2 * np.cos(ang) + x1 * np.sin(ang)], -1)


def _rms_np(x, scale, eps):
    return x / np.sqrt(np.mean(x * x, -1, keepdims=True) + eps) * scale


def _mla_numpy(u, p, cfg, scale=None, skip_norm=None):
    """Latent attention as the family writes it, for rows ``u`` (B, T, d):
    float64 NumPy, one row and one head at a time, the one rotated key of a
    token used by every head. ``scale`` and ``skip_norm`` build the wrong
    layers the tests tell from the right one."""
    p = jax.tree_util.tree_map(lambda x: np.asarray(x, np.float64), p)
    H, nope, rope, dv, rank = (cfg.num_heads, cfg.qk_nope_head_dim,
                               cfg.qk_rope_head_dim, cfg.v_head_dim,
                               cfg.kv_lora_rank)
    scale = (nope + rope) ** -0.5 if scale is None else scale
    norm = lambda x, name: x if skip_norm == name else _rms_np(
        x, p[name]["scale"], cfg.rms_eps)
    out = []
    for row in np.asarray(u, np.float64):
        t = row.shape[0]
        c_q = norm(row @ p["q_a"]["kernel"], "q_norm")
        q = (c_q @ p["q_b"]["kernel"]).reshape(t, H, nope + rope)
        kv = row @ p["kv_a"]["kernel"]
        c_kv = norm(kv[:, :rank], "kv_norm")
        k_rope = _rope_np(kv[:, rank:], cfg.rope_theta)
        kv_up = (c_kv @ p["kv_b"]["kernel"]).reshape(t, H, nope + dv)
        heads = []
        for j in range(H):
            q_j = np.concatenate([q[:, j, :nope],
                                  _rope_np(q[:, j, nope:], cfg.rope_theta)],
                                 -1)
            k_j = np.concatenate([kv_up[:, j, :nope], k_rope], -1)
            s = q_j @ k_j.T * scale
            s = np.where(np.tril(np.ones((t, t), bool)), s, -np.inf)
            w = np.exp(s - s.max(-1, keepdims=True))
            heads.append(w / w.sum(-1, keepdims=True) @ kv_up[:, j, nope:])
        out.append(np.concatenate(heads, -1) @ p["o"]["kernel"])
    return np.stack(out)


@pytest.mark.parametrize("attention", ["dense", "flash"])
def test_latent_attention_against_a_per_head_numpy_loop(attention):
    # 37 positions: no tile of 16 divides them
    cfg, layer, params, u = _attention_setup(
        37, attention=attention, flash_blocks=(16, 16))
    got = layer.apply({"params": params}, u)
    np.testing.assert_allclose(np.asarray(got), _mla_numpy(u, params, cfg),
                               atol=2e-5)


def test_latent_attention_gradients_against_the_numpy_loop():
    """Every leaf's gradient and the input's, along one random direction:
    the central difference of the NumPy loop in float64."""
    cfg, layer, params, u = _attention_setup(
        37, attention="flash", flash_blocks=(16, 16))
    weigh = np.asarray(jax.random.normal(jax.random.PRNGKey(6),
                                         (2, 37, cfg.d_model)), np.float64)
    grads = jax.grad(lambda p, u: jnp.sum(
        layer.apply({"params": p}, u) * weigh), argnums=(0, 1))(params, u)
    direction = jax.tree_util.tree_map(
        lambda x: np.asarray(jax.random.normal(
            jax.random.PRNGKey(x.size), x.shape), np.float64), (params, u))
    got = sum(float(np.sum(np.asarray(g, np.float64) * d)) for g, d in zip(
        jax.tree_util.tree_leaves(grads),
        jax.tree_util.tree_leaves(direction)))

    def at(eps):
        p, x = jax.tree_util.tree_map(
            lambda a, d: np.asarray(a, np.float64) + eps * d, (params, u),
            direction)
        return float(np.sum(_mla_numpy(x, p, cfg) * weigh))

    want = (at(1e-5) - at(-1e-5)) / 2e-5
    assert got == pytest.approx(want, rel=2e-4)
    assert all(float(jnp.max(jnp.abs(g))) > 0
               for g in jax.tree_util.tree_leaves(grads))


def _operands(monkeypatch, layer, params, u):
    """``(q, k, v, scale)`` as the layer hands them to the attention op."""
    seen = {}
    real = attention_ops.multihead_attention

    def spy(q, k, v, **kw):
        seen.update(q=q, k=k, v=v, scale=kw["scale"], causal=kw["causal"])
        return real(q, k, v, **kw)

    monkeypatch.setattr(attention_ops, "multihead_attention", spy)
    layer.apply({"params": params}, u)
    monkeypatch.undo()
    return seen


def test_the_rotated_key_is_one_head_shared_by_all(monkeypatch):
    cfg, layer, params, u = _attention_setup(T)
    nope, rank = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    seen = _operands(monkeypatch, layer, params, u)
    k = np.asarray(seen["k"])
    assert k.shape == (2, T, cfg.num_heads, nope + cfg.qk_rope_head_dim)
    assert seen["causal"] is True
    for j in range(1, cfg.num_heads):
        np.testing.assert_array_equal(k[:, :, j, nope:], k[:, :, 0, nope:])
        assert np.abs(k[:, :, j, :nope] - k[:, :, 0, :nope]).max() > 1e-3
    # perturbing W_kva's rope columns moves that part of every head's key
    # alike, and nothing else of what the kernel is handed
    moved = dict(params, kv_a={
        "kernel": params["kv_a"]["kernel"].at[:, rank:].add(0.1)})
    after = _operands(monkeypatch, layer, moved, u)
    k2 = np.asarray(after["k"])
    delta = k2[..., nope:] - k[..., nope:]
    assert np.abs(delta).max() > 1e-3
    for j in range(1, cfg.num_heads):
        np.testing.assert_array_equal(delta[:, :, j], delta[:, :, 0])
    np.testing.assert_array_equal(k2[..., :nope], k[..., :nope])
    np.testing.assert_array_equal(np.asarray(after["v"]),
                                  np.asarray(seen["v"]))
    np.testing.assert_array_equal(np.asarray(after["q"]),
                                  np.asarray(seen["q"]))


def test_the_plain_parts_and_the_values_take_no_position(monkeypatch):
    """The same input at every position: the per-head key part without
    position, the values and the plain part of the queries are then the same
    at every position, and the rotated parts are not."""
    cfg, layer, params, u = _attention_setup(T)
    nope = cfg.qk_nope_head_dim
    same = jnp.broadcast_to(u[:, :1], u.shape)
    seen = {k: np.asarray(v) for k, v in _operands(
        monkeypatch, layer, params, same).items() if k in "qkv"}
    for name, part in (("k_nope", seen["k"][..., :nope]), ("v", seen["v"]),
                       ("q_nope", seen["q"][..., :nope])):
        np.testing.assert_allclose(part, np.broadcast_to(
            part[:, :1], part.shape), atol=1e-6, err_msg=name)
    for name, part in (("k_rope", seen["k"][..., nope:]),
                       ("q_rope", seen["q"][..., nope:])):
        assert np.abs(part[:, 1:] - part[:, :1]).max() > 1e-3, name


@pytest.mark.parametrize("width,same", [("nope + rope", True),
                                        ("nope", False), ("rope", False)])
def test_the_scale_is_that_of_the_whole_query(monkeypatch, width, same):
    cfg, layer, params, u = _attention_setup(T)
    nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    scale = {"nope + rope": nope + rope, "nope": nope,
             "rope": rope}[width] ** -0.5
    assert (_operands(monkeypatch, layer, params, u)["scale"]
            == pytest.approx(scale)) == same
    got = np.asarray(layer.apply({"params": params}, u))
    off = np.abs(got - _mla_numpy(u, params, cfg, scale=scale)).max()
    assert bool(off < 2e-5) == same, off


@pytest.mark.parametrize("name", ["q_norm", "kv_norm"])
def test_both_inner_norms_are_live(name):
    cfg, layer, params, u = _attention_setup(T)
    got = np.asarray(layer.apply({"params": params}, u))
    without = _mla_numpy(u, params, cfg, skip_norm=name)
    assert np.abs(got - without).max() > 1e-3
    # the norm sits between the two halves of its projection: it normalises
    # the bottleneck's width and carries a gain of that width
    rank = cfg.q_lora_rank if name == "q_norm" else cfg.kv_lora_rank
    assert params[name]["scale"].shape == (rank,)


# ---------------------------------------------------------------------------
# the blocks
# ---------------------------------------------------------------------------

def test_layer_zero_is_dense_and_the_others_routed_and_shared():
    cfg, model, params, tokens, _ = _setup()
    kinds = [sorted(k for k in params[f"h{i}"] if not k.startswith("norm"))
             for i in range(cfg.num_layers)]
    assert kinds == [["attn", "mlp"], ["attn", "moe", "shared"],
                     ["attn", "moe", "shared"]]
    assert params["h0"]["mlp"]["w_gate"]["kernel"].shape == (32, cfg.d_ff)
    assert params["h1"]["shared"]["w_gate"]["kernel"].shape == (
        32, cfg.d_expert * cfg.shared_experts)
    assert params["h1"]["moe"]["router"].shape == (32, cfg.experts_total)
    assert params["h1"]["moe"]["w_gate"].shape == (2, 32, cfg.d_expert)
    assert sorted(params["mtp"]) == ["block", "eh_proj", "norm_e", "norm_h",
                                     "norm_s"]
    assert sorted(k for k in params["mtp"]["block"]
                  if not k.startswith("norm")) == ["attn", "moe", "shared"]
    assert params["mtp"]["eh_proj"]["kernel"].shape == (64, 32)
    # two dense layers told so build another tree
    other = glm.Glm4MoeLite(glm.Glm4MoeLiteConfig.tiny(num_dense_layers=2))
    tree = jax.eval_shape(lambda: other.init(jax.random.PRNGKey(0),
                                             tokens))["params"]
    assert "mlp" in tree["h1"] and "moe" in tree["h2"]


@pytest.mark.parametrize("changes,match", [
    (dict(n_group=2), "one group alone"),
    (dict(topk_group=2), "one group alone"),
    (dict(mtp=2), "one module or none"),
    (dict(attention="flash", v_head_dim=8), "one head size")])
def test_the_model_refuses_what_it_cannot_build(changes, match):
    model = glm.Glm4MoeLite(glm.Glm4MoeLiteConfig.tiny(**changes))
    with pytest.raises(ValueError, match=match):
        model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))


def test_the_published_defaults():
    cfg = glm.Glm4MoeLiteConfig()
    assert (cfg.vocab_size, cfg.num_layers, cfg.num_dense_layers,
            cfg.num_heads, cfg.d_model, cfg.q_lora_rank, cfg.kv_lora_rank,
            cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim,
            cfg.d_ff, cfg.d_expert, cfg.experts_total, cfg.experts_held,
            cfg.top_k, cfg.shared_experts, cfg.routed_scale, cfg.mtp) == (
                154880, 47, 1, 20, 2048, 768, 512, 192, 64, 256, 10240, 1536,
                64, (0, 64), 4, 1, 1.8, 1)
    from horovod_tpu import models
    assert models.Glm4MoeLite is glm.Glm4MoeLite
    assert models.Glm4MoeLiteConfig is glm.Glm4MoeLiteConfig


def test_serving_refuses_the_family_and_says_why():
    from horovod_tpu.models import generate as gen
    cfg = glm.Glm4MoeLiteConfig.tiny()
    with pytest.raises(TypeError,
                       match="trained here and not served.*latent"):
        gen.decode_family(cfg)
    with pytest.raises(TypeError, match="trained here and not served"):
        gen.generate(glm.Glm4MoeLite(cfg), {}, jnp.zeros((1, 4), jnp.int32),
                     2)


# ---------------------------------------------------------------------------
# the routing rule as this family sets it, and the shared expert
# ---------------------------------------------------------------------------

def _layer(n=64, d=32, f=16, experts=8, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 9)
    normal = lambda k, shape, s: jax.random.normal(k, shape, jnp.float32) * s
    moe_p = {"router": normal(ks[1], (d, experts), 0.3),
             "w_gate": normal(ks[2], (experts, d, f), 0.2),
             "w_up": normal(ks[3], (experts, d, f), 0.2),
             "w_down": normal(ks[4], (experts, f, d), 0.2)}
    shared = {"w_gate": {"kernel": normal(ks[6], (d, f), 0.2)},
              "w_up": {"kernel": normal(ks[7], (d, f), 0.2)},
              "w_down": {"kernel": normal(ks[8], (f, d), 0.2)}}
    return (normal(ks[0], (n, d), 1.0), moe_p, shared,
            normal(ks[5], (experts,), 0.3))


_RULE = dict(score="sigmoid", norm_eps=1e-20, scale=1.8, dtype=jnp.float32)


def _share(x, p, bias, first, held, top_k, **rule):
    rule = dict(_RULE, **rule)
    cut = slice(first, first + held)
    return moe.routed_share(x, p["router"], p["w_gate"][cut], p["w_up"][cut],
                            p["w_down"][cut], first=first, top_k=top_k,
                            select_bias=bias, **rule)


def _uncut(x, p, shared, bias, top_k):
    """The whole layer by the reference: every routed expert held, and the
    shared expert."""
    with jax.default_matmul_precision("highest"):
        routed = ref._experts(x, p, bias, top_k=top_k, norm_topk=True,
                              routed_scale=1.8, experts_first=0)[0]
        return routed + ref._swiglu(x, shared)


def test_the_block_hands_the_layer_its_rule(monkeypatch):
    """1e-20 in the normaliser, 1.8 on the gates, sigmoid scores and the
    bias for the choice: what the block tells ``routed_share``."""
    cfg, model, params, tokens, bias = _setup()
    seen = []
    real = moe.routed_share

    def spy(*args, **kw):
        seen.append(kw)
        return real(*args, **kw)

    monkeypatch.setattr(moe, "routed_share", spy)
    model.apply({"params": params}, tokens, bias)
    assert len(seen) == 3                       # h1, h2 and the module's
    for kw, row in zip(seen, (1, 2, 3)):
        assert (kw["score"], kw["norm_eps"], kw["scale"], kw["top_k"]) == (
            "sigmoid", 1e-20, 1.8, 4)
        np.testing.assert_array_equal(np.asarray(kw["select_bias"]),
                                      np.asarray(bias[row]))


def test_the_normaliser_carries_one_e_minus_twenty():
    x, p, _, bias = _layer()
    # scores so small that 1e-20 shows: sigmoid(-46) ~ 1.05e-20
    p = dict(p, router=jnp.zeros_like(p["router"]).at[0].set(-46.0),
             w_gate=jnp.ones_like(p["w_gate"]),
             w_up=jnp.ones_like(p["w_up"]), w_down=jnp.ones_like(p["w_down"]))
    x = jnp.zeros_like(x).at[:, 0].set(1.0)
    whole = lambda **rule: float(sum(
        _share(x, p, None, first, 2, 2, **rule)[0] for first in range(0, 8, 2)
    )[0, 0])
    s = float(jax.nn.sigmoid(jnp.float32(-46.0)))
    assert 0.5e-20 < s < 2e-20
    unit = whole(norm_eps=0.0)                  # gates sum to 1.8
    assert whole() == pytest.approx(unit * 2 * s / (2 * s + 1e-20), rel=1e-4)
    assert whole() < 0.8 * unit
    assert whole(norm_eps=1e-6) < 1e-10 * unit  # another family's epsilon


def test_the_gates_are_scaled_by_one_point_eight():
    x, p, _, bias = _layer()
    one = sum(_share(x, p, bias, f, 2, 4, scale=1.0)[0]
              for f in range(0, 8, 2))
    scaled = sum(_share(x, p, bias, f, 2, 4)[0] for f in range(0, 8, 2))
    np.testing.assert_allclose(np.asarray(scaled), 1.8 * np.asarray(one),
                               rtol=1e-5, atol=1e-6)


def test_a_bias_changes_the_chosen_set_and_the_gates_stay_the_scores():
    x, p, shared, bias = _layer()
    out, aux = _share(x, p, bias, 0, 8, 2)
    _, plain = jax.lax.top_k(jax.nn.sigmoid(x @ p["router"]), 2)
    moved = (np.asarray(aux["choice"])[:, :, None]
             != np.asarray(plain)[:, None, :]).all(-1)
    assert 0 < moved.sum() < moved.size
    # the reference weighs the chosen by their unbiased scores
    want = _uncut(x, p, shared, bias, 2) - ref._swiglu(x, shared)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5)
    # gates from the biased scores would read otherwise
    s = jax.nn.sigmoid(x @ p["router"]) + bias
    g = jnp.take_along_axis(s, aux["choice"], axis=-1)
    g = 1.8 * g / g.sum(-1, keepdims=True)
    every = jnp.einsum("ned,edf->nef", jnp.broadcast_to(
        x[:, None], (64, 8, 32)), p["w_gate"])
    every = jnp.einsum("nef,efd->ned", jax.nn.silu(every) * jnp.einsum(
        "nd,edf->nef", x, p["w_up"]), p["w_down"])
    biased = jnp.einsum("nk,nkd->nd", g, jnp.take_along_axis(
        every, aux["choice"][..., None], axis=1))
    assert np.abs(np.asarray(biased) - np.asarray(out)).max() > 1e-3


@pytest.mark.parametrize("top_k", [2, 4])
def test_the_eight_shares_and_the_shared_expert_once_add_up(top_k):
    """Sixteen experts over eight holders of two: the routed parts of all
    eight plus the shared expert **once** are the uncut layer; counted with
    every holder, as a careless sum of whole blocks would, it is not."""
    x, p, shared, bias = _layer(experts=16)
    layer = moe.SharedExpert(16, jnp.float32)
    everyone = layer.apply({"params": shared}, x)
    total, given = jnp.zeros_like(x), 0
    for first in range(0, 16, 2):
        out, aux = _share(x, p, bias, first, 2, top_k)
        total = total + out
        given += int(aux["group_sizes"].sum())
    assert given == x.shape[0] * top_k          # every assignment, once
    want = np.asarray(_uncut(x, p, shared, bias, top_k))
    np.testing.assert_allclose(np.asarray(total + everyone), want, atol=2e-5)
    assert np.abs(np.asarray(total + 8 * everyone) - want).max() > 1e-2
    assert np.abs(np.asarray(total) - want).max() > 1e-2


def test_under_an_ep_axis_the_shared_expert_is_not_summed_four_times():
    """Four peers, rows and experts sharded over ``ep``: every peer ends
    with the whole block's result for its own rows: all eight routed
    experts and the shared expert once."""
    cfg = glm.Glm4MoeLiteConfig.tiny(experts_held=(0, 2), dtype=jnp.float32,
                                     ep_axis="ep")
    whole = dataclasses.replace(cfg, experts_held=(0, 8), ep_axis=None)
    x = jax.random.normal(jax.random.PRNGKey(7), (4, 16, cfg.d_model))
    bias = 0.1 * jax.random.normal(jax.random.PRNGKey(8),
                                   (cfg.experts_total,))
    params = glm.Block(whole, 1).init(jax.random.PRNGKey(9), x,
                                      bias)["params"]
    specs = jax.tree_util.tree_map(lambda _: P(), params)
    specs["moe"] = {k: P() if k == "router" else P("ep")
                    for k in params["moe"]}
    mesh = Mesh(np.array(jax.devices()[:4]), ("ep",))
    out = jax.jit(jax.shard_map(
        lambda p, x, b: glm.Block(cfg, 1).apply({"params": p}, x, b),
        mesh=mesh, in_specs=(specs, P("ep"), P()), out_specs=P("ep")))(
            params, x, bias)
    kw = dict(_kw(whole), dtype="float32", router_dtype=None, shared=True)
    with jax.default_matmul_precision("highest"):
        want, _ = ref._block(x, params, bias, False, kw)
        less, _ = ref._block(x, params, bias, False, dict(kw, shared=False))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)
    # what a shared expert inside the reduce-scatter would have given
    four = np.asarray(want) + 3 * (np.asarray(want) - np.asarray(less))
    assert np.abs(np.asarray(out) - four).max() > 1e-2


def test_the_routed_layer_is_as_it_was():
    """The shared expert is a sibling: ``RoutedExperts`` holds the four
    leaves it held and its default call traces without a second SwiGLU (the
    parent's jaxpr, compared once against a checkout of it: CHANGES.md)."""
    x, p, _, _ = _layer()
    layer = moe.RoutedExperts(8, (0, 2), 2, 16)
    params = layer.init(jax.random.PRNGKey(0), x[None])["params"]
    assert sorted(params) == ["router", "w_down", "w_gate", "w_up"]
    text = str(jax.make_jaxpr(
        lambda p: layer.apply({"params": p}, x[None]))(params))
    assert text.count(" logistic ") == 1        # the experts' one silu


# ---------------------------------------------------------------------------
# the multi-token-prediction module
# ---------------------------------------------------------------------------

def _mtp_term(cfg, params, tokens, bias, embed_shift=1, hidden_shift=0,
              target_shift=2):
    """The module's term by hand from the model's parts: the trunk's output
    at ``t + hidden_shift``, the embedding of token ``t + embed_shift``,
    scored against token ``t + target_shift`` over the ``T - 2`` positions
    whose three all lie in the row."""
    trunk = glm.Glm4MoeLite(dataclasses.replace(cfg, mtp=0))
    hidden, _ = trunk.apply(
        {"params": {k: v for k, v in params.items() if k != "mtp"}}, tokens,
        bias[:cfg.num_layers])
    ahead = params["wte"][jnp.roll(tokens, -embed_shift, axis=1)]
    out = glm.MTP(cfg).apply(
        {"params": params["mtp"]}, jnp.roll(hidden, -hidden_shift, axis=1),
        ahead, bias[cfg.num_layers])
    logits = out @ params["lm_head"].T
    ll = jnp.take_along_axis(
        jax.nn.log_softmax(logits, axis=-1),
        jnp.roll(tokens, -target_shift, axis=1)[..., None], axis=-1)[..., 0]
    return float(-jnp.mean(ll[:, :-2]))


@pytest.mark.parametrize("shifts,same", [
    (dict(), True),
    (dict(embed_shift=0), False), (dict(embed_shift=2), False),
    (dict(hidden_shift=1), False),
    (dict(target_shift=1), False), (dict(target_shift=3), False)],
    ids=["z_t, e_t+1 -> token_t+2", "e_t", "e_t+2", "z_t+1", "token_t+1",
         "token_t+3"])
def test_the_module_predicts_two_ahead_from_the_trunk_and_the_next_token(
        shifts, same):
    cfg, model, params, tokens, bias = _setup()
    _, want = glm.loss_terms(model, params, tokens, bias)
    got = _mtp_term(cfg, params, tokens, bias, **shifts)
    assert (abs(got - float(want)) < 1e-5 * float(want)) == same, (got, want)


def test_the_module_shares_the_embedding_and_the_head():
    cfg, model, params, tokens, bias = _setup()
    rows = [path for path, leaf in jax.tree_util.tree_leaves_with_path(params)
            if cfg.vocab_size in leaf.shape]
    assert sorted(jax.tree_util.keystr(p) for p in rows) == [
        "['lm_head']", "['wte']"]
    # its own term reads both: perturbing either moves it
    _, before = glm.loss_terms(model, params, tokens, bias)
    for name in ("wte", "lm_head"):
        moved = dict(params, **{name: params[name] * 1.5})
        _, after = glm.loss_terms(model, moved, tokens, bias)
        assert abs(float(after) - float(before)) > 1e-4, name


def test_the_modules_gradient_reaches_the_trunk():
    cfg, model, params, tokens, bias = _setup()
    term = lambda i: jax.grad(
        lambda p: glm.loss_terms(model, p, tokens, bias)[i])(params)
    mtp, main = term(1), term(0)
    for name in ("wte", "lm_head", "h0", "h2", "norm_f", "mtp"):
        assert all(float(jnp.max(jnp.abs(g))) > 0
                   for g in jax.tree_util.tree_leaves(mtp[name])), name
    # and the next-token term does not know the module
    assert not any(np.asarray(g).any()
                   for g in jax.tree_util.tree_leaves(main["mtp"]))
    assert np.asarray(main["h0"]["attn"]["q_a"]["kernel"]).any()


def test_the_bias_has_a_row_for_the_module_and_takes_no_gradient():
    cfg, model, params, tokens, bias = _setup()
    base = float(glm.loss_fn(model, params, tokens, bias))
    assert abs(base - float(glm.loss_fn(model, params, tokens))) > 1e-6
    g = jax.grad(lambda b: glm.loss_fn(model, params, tokens, b))(bias)
    assert not np.asarray(g).any()
    # the dense layer's row is never read; the module's is
    assert float(glm.loss_fn(model, params, tokens,
                             bias.at[0].add(10.0))) == base
    tilted = bias.at[cfg.num_layers, 5].add(10.0)
    main, mtp = glm.loss_terms(model, params, tokens, tilted)
    was_main, was_mtp = glm.loss_terms(model, params, tokens, bias)
    assert float(main) == float(was_main)
    assert abs(float(mtp) - float(was_mtp)) > 1e-6


# ---------------------------------------------------------------------------
# names and gauges
# ---------------------------------------------------------------------------

def _gauge(name, program):
    import horovod_tpu as hvd
    return [s["value"] for s in hvd.metrics.snapshot()["gauges"].get(name, ())
            if s["labels"].get("program") == program]


@pytest.mark.parametrize("mtp", [1, 0])
def test_the_manifest_of_a_traced_step(mtp):
    from horovod_tpu.ops.flash_attention import causal_tiles
    cfg, model, params, tokens, bias = _setup(
        attention="flash", flash_blocks=(16, 16), mtp=mtp,
        dtype=jnp.bfloat16)
    name = f"glm4_step_{mtp}"
    with tracing.program(name):
        jax.make_jaxpr(lambda p: glm.loss_fn(model, p, tokens,
                                             bias[:3 + mtp]))(params)
    visited, total = causal_tiles(T, 16, 16)
    layers = cfg.num_layers + mtp
    want = {"moe_rows_bound": 2 * T * 2, "moe_rows_tight": 2 * T * 2,
            "causal_tiles_visited": visited,
            "causal_tiles_total": total, "mtp_modules": mtp,
            # positions x attention layers x 4 heads x (16 + 16) x 2 B
            "mla_kv_expanded_bytes": 2 * T * layers * 4 * 32 * 2,
            # positions x attention layers x (16 + 4) x 2 B
            "mla_latent_bytes": 2 * T * layers * 20 * 2}
    for gauge, value in want.items():
        assert _gauge(gauge, name) == [value], gauge


def test_routing_load_from_the_auxiliary_output():
    cfg, model, params, tokens, bias = _setup()
    _, kept = model.apply({"params": params}, tokens, bias,
                          mutable=["intermediates"])
    assert sorted(kept["intermediates"]) == ["h1", "h2", "mtp"]
    for layer in (kept["intermediates"]["h1"],
                  kept["intermediates"]["mtp"]["block"]):
        choice = np.asarray(layer["moe"]["choice"][0])
        sizes = np.asarray(layer["moe"]["group_sizes"][0])
        assert choice.shape == (2 * T, 4)
        assert sizes.tolist() == [int((choice == e).sum()) for e in (2, 3)]
