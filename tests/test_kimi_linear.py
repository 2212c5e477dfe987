"""The decoder of KDA layers beside position-free latent attention, a dense
SwiGLU first and routed experts beside a shared one after
(``models/kimi_linear.py``), on the CPU at a small size with the published
kinds of layer in their published order: system against the plain
reference of the benchmark on seeded weights (loss, every gradient leaf,
and every KDA layer's output against the reference's recurrence), the
layer order 3 : 1 after the dense layer, latent attention without
positions or a query bottleneck and with values narrower than its keys, and
the shares of an expert layer with the shared expert **once** adding up to
the uncut layer."""

import dataclasses
import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import kimi_linear as kl
from horovod_tpu.ops import moe

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
sys.path.insert(0, BENCH)
from reference import kimi_linear_ref as ref  # noqa: E402
from reference import glm4_moe_lite_ref  # noqa: E402

T = 40      # one chunk of 48, the row padded (tests/test_kda.py chunks more)


def _kw(cfg, **extra):
    return dict(mixers=tuple(m for m, _ in kl.layer_kinds(cfg)),
                num_dense_layers=cfg.num_dense_layers,
                kda_heads=cfg.kda_heads, num_heads=cfg.num_heads,
                qk_nope_head_dim=cfg.qk_nope_head_dim, eps=cfg.rms_eps,
                top_k=cfg.top_k, norm_topk=cfg.norm_topk,
                routed_scale=cfg.routed_scale,
                experts_first=cfg.experts_held[0], **extra)


def _setup(**kw):
    """The dense KDA block, then KDA, KDA, latent attention, KDA, all
    routed with a shared expert; 8 experts of which 2 held (given rows in
    every routed layer), top-2, a bias, embedding rows of size 1, fp32."""
    kw.setdefault("dtype", jnp.float32)
    cfg = kl.KimiLinearConfig.tiny(experts_held=(2, 2), embed_std=1.0, **kw)
    model = kl.KimiLinear(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, T), 0,
                                cfg.vocab_size)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    # the family's size of bias; at these widths one of 0.1 outweighs the
    # scores and crowds a layer onto two experts
    bias = 0.02 * jax.random.normal(jax.random.PRNGKey(3),
                                    (cfg.num_layers, cfg.experts_total))
    return cfg, model, params, tokens, bias


@functools.lru_cache(maxsize=None)
def _reference_side():
    cfg, _, params, tokens, bias = _setup()
    tree = ref.from_system(params, cfg.num_layers)
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(
            lambda r: ref.loss(r, tokens, bias, **_kw(cfg))))(tree)


@pytest.mark.parametrize("attention,remat,policy", [
    ("dense", False, "full"), ("flash", False, "full"),
    ("flash", True, "full"), ("flash", True, "dots")])
def test_loss_and_gradients_match_the_reference(attention, remat, policy):
    cfg, model, params, tokens, bias = _setup(
        attention=attention, remat=remat, remat_policy=policy,
        flash_blocks=(16, 16))
    want, want_grads = _reference_side()
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p: kl.loss_fn(model, p, tokens, bias)))(params)
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


def test_reference_loss_and_grad_norm_by_micro_batches():
    cfg, model, params, tokens, bias = _setup()
    tree = ref.from_system(params, cfg.num_layers)
    whole = ref.loss_and_grad_norm(tree, tokens, bias, micro=2, **_kw(cfg))
    rows = ref.loss_and_grad_norm(tree, tokens, bias, micro=1, **_kw(cfg))
    np.testing.assert_allclose(whole, rows, rtol=1e-5)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_every_kda_layer_is_the_reference_recurrence(dtype):
    """What each KDA layer's operator gives, ``o_proj`` included, on the
    program's own block inputs, against the reference's position-by-position
    recurrence: the comparison the benchmark's check makes (families/
    kimi_linear.kda_faults), at float32 and at the bfloat16 compute dtype
    the cell runs."""
    from families import kimi_linear as fam
    cfg, model, params, tokens, bias = _setup(dtype=jnp.dtype(dtype))
    _, _, _, kda_in, kda_out, core = fam.routing_of(cfg, params, tokens)
    assert kda_in.shape == kda_out.shape == (4, 2, T, cfg.d_model)
    tree = ref.from_system(params, cfg.num_layers)
    theirs = np.asarray(ref.kda_outputs(tree, kda_in, **_kw(cfg)))
    differ = fam.kda_differs(kda_out, theirs)
    assert (differ < (1e-5 if dtype == "float32" else 2e-2)).all(), differ
    # the core alone on its own inputs: the chunked form against the
    # recurrence, and a state kept in bfloat16 further off
    cores = fam.core_differs(core)
    assert (cores < 1e-4).all(), cores
    assert (fam.core_differs(core, state_dtype="bfloat16")
            > 10 * cores).all()
    # and the reference's departures read far off
    for extra in (dict(scalar_decay=True), dict(delta=False),
                  dict(l2=False)):
        off = np.asarray(ref.kda_outputs(tree, kda_in,
                                         **_kw(cfg, **extra)))
        assert (fam.kda_differs(off, theirs) > 0.05).all(), extra


def test_the_layers_are_the_dense_one_and_one_period_of_three_to_one():
    """The configuration's cut: published layers 1 to 5, KDA with the dense
    SwiGLU, then KDA, KDA, latent attention, KDA, routed; and the published
    27 keep 3 KDA to 1 latent attention (with the last layer latent too)."""
    from families import kimi_linear as fam
    with open(os.path.join(BENCH, "configs",
                           "kimi-linear-48b-a3b-train.json")) as f:
        config = json.load(f)
    cfg = fam.program_config(config)
    assert kl.layer_kinds(cfg) == [("kda", "dense"), ("kda", "routed"),
                                   ("kda", "routed"), ("mla", "routed"),
                                   ("kda", "routed")]
    full = kl.layer_kinds(kl.KimiLinearConfig())
    mixers = "".join("M" if m == "mla" else "K" for m, _ in full)
    assert mixers == "KKKM" * 6 + "KKM"
    assert [f for _, f in full] == ["dense"] + ["routed"] * 26
    published = config["published"]["linear_attn_config"]
    assert tuple(i - 1 for i in published["full_attn_layers"]) == \
        kl.KimiLinearConfig().mla_layers


def test_the_published_defaults_and_the_gauges():
    cfg = kl.KimiLinearConfig()
    assert (cfg.vocab_size, cfg.num_layers, cfg.d_model, cfg.kda_heads,
            cfg.kda_head_dim, cfg.conv_size, cfg.num_heads, cfg.q_lora_rank,
            cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim, cfg.mla_use_nope, cfg.d_ff, cfg.d_expert,
            cfg.experts_total, cfg.top_k, cfg.shared_experts,
            cfg.routed_scale) == (
                163840, 27, 2304, 32, 128, 4, 32, None, 512, 128, 64, 128,
                True, 9216, 1024, 256, 8, 1, 2.446)


def test_serving_refuses_the_family_and_says_why():
    from horovod_tpu.models import generate as gen
    cfg = kl.KimiLinearConfig.tiny()
    with pytest.raises(TypeError,
                       match="trained here and not served.*recurrent"):
        gen.decode_family(cfg)


def test_latent_attention_takes_no_position_and_no_bottleneck():
    """With ``q_lora_rank`` None the queries come from one ``q`` product;
    with ``mla_use_nope`` no position enters the scores, so the last
    position's output does not change when the positions before it are
    shuffled; the values are ``v_head_dim`` wide, narrower than the keys,
    and the flash path gives what the dense one gives."""
    cfg = kl.KimiLinearConfig.tiny(dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 32, cfg.d_model))
    layer = kl.latent_attention(cfg)
    params = layer.init(jax.random.PRNGKey(4), x)["params"]
    assert set(params) == {"q", "kv_a", "kv_norm", "kv_b", "o"}
    assert params["kv_b"]["kernel"].shape[1] == cfg.num_heads * (
        cfg.qk_nope_head_dim + cfg.v_head_dim)
    out = layer.apply({"params": params}, x)
    order = np.concatenate([np.random.default_rng(0).permutation(31), [31]])
    shuffled = layer.apply({"params": params}, x[:, order])
    np.testing.assert_allclose(np.asarray(shuffled[:, -1]),
                               np.asarray(out[:, -1]), atol=1e-5)
    assert np.abs(np.asarray(shuffled[:, :-1] - out[:, :-1])).max() > 1e-3
    flash = kl.latent_attention(dataclasses.replace(
        cfg, attention="flash", flash_blocks=(16, 16)))
    np.testing.assert_allclose(np.asarray(flash.apply({"params": params},
                                                      x)),
                               np.asarray(out), atol=1e-4)


@pytest.mark.parametrize("top_k", [2, 8])
def test_the_shares_and_the_shared_expert_once_add_up(top_k):
    """Thirty-two experts over four holders of eight, the family's rule
    (sigmoid scores, the top-k of score plus bias, gates over their sum +
    1e-20, times 2.446): the routed parts of all four plus the shared expert
    **once** are the uncut layer by the reference."""
    ks = jax.random.split(jax.random.PRNGKey(5), 9)
    n, d, f, experts = 48, 32, 16, 32
    normal = lambda k, shape, s: jax.random.normal(k, shape, jnp.float32) * s
    x = normal(ks[0], (n, d), 1.0)
    p = {"router": normal(ks[1], (d, experts), 0.3),
         "w_gate": normal(ks[2], (experts, d, f), 0.2),
         "w_up": normal(ks[3], (experts, d, f), 0.2),
         "w_down": normal(ks[4], (experts, f, d), 0.2)}
    shared = {"w_gate": {"kernel": normal(ks[6], (d, f), 0.2)},
              "w_up": {"kernel": normal(ks[7], (d, f), 0.2)},
              "w_down": {"kernel": normal(ks[8], (f, d), 0.2)}}
    bias = normal(ks[5], (experts,), 0.3)
    everyone = moe.SharedExpert(f, jnp.float32).apply({"params": shared}, x)
    total, given = jnp.zeros_like(x), 0
    for first in range(0, experts, 8):
        cut = slice(first, first + 8)
        out, aux = moe.routed_share(
            x, p["router"], p["w_gate"][cut], p["w_up"][cut],
            p["w_down"][cut], first=first, top_k=top_k, select_bias=bias,
            score="sigmoid", norm_eps=1e-20, scale=2.446, dtype=jnp.float32)
        total, given = total + out, given + int(aux["group_sizes"].sum())
    assert given == n * top_k                   # every assignment, once
    with jax.default_matmul_precision("highest"):
        routed = glm4_moe_lite_ref._experts(
            x, p, bias, top_k=top_k, norm_topk=True, routed_scale=2.446,
            experts_first=0)[0]
        want = np.asarray(routed + glm4_moe_lite_ref._swiglu(x, shared))
    np.testing.assert_allclose(np.asarray(total + everyone), want, atol=5e-5)
    assert np.abs(np.asarray(total + 4 * everyone) - want).max() > 1e-2
