"""The decoder of position-free global attention beside sliding-window
attention with RoPE, whose router reads a block's input before attention and
whose experts are ReGLU (``models/smallthinker.py``, the window of
``ops/flash_attention``, ``route_from`` / ``act`` of ``ops/moe``), on the CPU
at a small size with the published kinds of layer: system against the plain
reference of the benchmark on seeded weights, attention by layer kind against
a NumPy loop, the shares of an expert layer adding up to the uncut layer, and
that the four accepted families' steps trace as they did."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu import tracing
from horovod_tpu.models import smallthinker as st
from horovod_tpu.ops import moe

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark"))
from reference import smallthinker_ref as ref  # noqa: E402

T = 32


def _kw(cfg):
    return dict(sliding_window_layout=cfg.sliding_window_layout,
                rope_layout=cfg.rope_layout,
                sliding_window=cfg.sliding_window, num_heads=cfg.num_heads,
                num_kv_heads=cfg.num_kv_heads, eps=cfg.rms_eps,
                rope_theta=cfg.rope_theta, top_k=cfg.top_k,
                norm_topk=cfg.norm_topk, experts_first=cfg.experts_held[0])


def _setup(**kw):
    """One period (global without positions, three windows of 8 with RoPE);
    8 experts of which 4 held, top-2, T 32, fp32, embedding rows N(0, 1)."""
    base = dict(experts_held=(2, 4), dtype=jnp.float32, embed_std=1.0)
    base.update(kw)
    cfg = st.SmallThinkerConfig.tiny(**base)
    model = st.SmallThinker(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, T), 0,
                                cfg.vocab_size)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    return cfg, model, params, tokens


# ---------------------------------------------------------------------------
# system against the reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("attention,remat,policy", [
    ("dense", False, "full"), ("flash", False, "full"),
    ("flash", True, "full"), ("flash", True, "dots")])
def test_loss_and_gradients_match_the_reference(attention, remat, policy):
    cfg, model, params, tokens = _setup(
        attention=attention, remat=remat, remat_policy=policy,
        flash_blocks=(16, 16))
    loss, grads = jax.value_and_grad(
        lambda p: st.loss_fn(model, p, tokens))(params)
    tree = ref.from_system(params, cfg.num_layers)
    want, want_grads = ref.loss_and_grad(tree, tokens, micro=1, **_kw(cfg))
    np.testing.assert_allclose(float(loss), want, rtol=1e-5)
    got = ref.from_system(grads, cfg.num_layers)
    assert (jax.tree_util.tree_structure(got)
            == jax.tree_util.tree_structure(want_grads))
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree_util.tree_leaves(want_grads)):
        scale = float(jnp.max(jnp.abs(b)))
        assert scale > 0, path
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-5 * scale, err_msg=str(path))


@pytest.mark.parametrize("attention", ["dense", "flash"])
def test_the_choices_are_the_references(attention):
    cfg, model, params, tokens = _setup(attention=attention,
                                        flash_blocks=(16, 16))
    _, kept = model.apply({"params": params}, tokens,
                          mutable=["intermediates"])
    tree = ref.from_system(params, cfg.num_layers)
    theirs = np.asarray(ref.choices(tree, tokens, **_kw(cfg)))
    assert theirs.shape == (cfg.num_layers, 2, T, cfg.top_k)
    for i in range(cfg.num_layers):
        moe_kept = kept["intermediates"][f"h{i}"]["moe"]
        mine = np.asarray(moe_kept["choice"][0]).reshape(2, T, cfg.top_k)
        np.testing.assert_array_equal(np.sort(mine), np.sort(theirs[i]))
        sizes = np.asarray(moe_kept["group_sizes"][0])
        assert sizes.tolist() == [int((mine == e).sum())
                                  for e in range(2, 6)]


def test_reference_loss_and_grad_norm_by_micro_batches():
    cfg, model, params, tokens = _setup()
    tree = ref.from_system(params, cfg.num_layers)
    whole = ref.loss_and_grad_norm(tree, tokens, micro=2, **_kw(cfg))
    rows = ref.loss_and_grad_norm(tree, tokens, micro=1, **_kw(cfg))
    np.testing.assert_allclose(whole, rows, rtol=1e-5)
    low = ref.loss_and_grad_norm(tree, tokens, micro=1, dtype="bfloat16",
                                 **_kw(cfg))
    # another precision gives another number, and not a far one
    assert 1e-6 < abs(low[0] - whole[0]) / whole[0] < 5e-2
    # and the departures the controls compute are other models
    for other in (dict(act="silu"), dict(route_after=True)):
        got = ref.loss_and_grad_norm(tree, tokens, micro=2, **_kw(cfg),
                                     **other)
        assert abs(got[0] - whole[0]) / whole[0] > 1e-5, other


def test_the_reference_in_blocks_of_queries_is_the_reference_whole(
        monkeypatch):
    """A row cut into passes of queries and of head positions gives what
    the row at once gives: at T 16,384 only the first fits the chip."""
    cfg, model, params, tokens = _setup()
    tree = ref.from_system(params, cfg.num_layers)
    whole = ref.loss_and_grad_norm(tree, tokens, micro=1, **_kw(cfg))
    monkeypatch.setattr(ref, "QUERY_BLOCK", 8)
    ref.ref_microbatch.clear_cache()
    try:
        cut = ref.loss_and_grad_norm(tree, tokens, micro=1, **_kw(cfg))
    finally:
        ref.ref_microbatch.clear_cache()
    np.testing.assert_allclose(cut, whole, rtol=1e-5)


# ---------------------------------------------------------------------------
# attention by the layer's kind
# ---------------------------------------------------------------------------

def _attention_by_hand(u, p, cfg, rotated, window):
    """One row (T, d) by a NumPy loop over heads, queries and keys."""
    u = np.asarray(u, np.float64)
    H, Hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    t = u.shape[0]
    w = {k: np.asarray(v["kernel"], np.float64) for k, v in p.items()}
    q = (u @ w["wq"]).reshape(t, H, hd)
    k = (u @ w["wk"]).reshape(t, Hkv, hd)
    v = (u @ w["wv"]).reshape(t, Hkv, hd)
    if rotated:
        half = hd // 2
        freq = cfg.rope_theta ** (-np.arange(half) / half)
        ang = np.arange(t)[:, None] * freq[None, :]

        def rope(x):
            x1, x2 = x[..., :half], x[..., half:]
            c, s = np.cos(ang)[:, None], np.sin(ang)[:, None]
            return np.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)
        q, k = rope(q), rope(k)
    out = np.zeros((t, H, hd))
    for h in range(H):
        kv = h // (H // Hkv)
        for i in range(t):
            lo = 0 if window is None else max(0, i - window + 1)
            s = q[i, h] @ k[lo:i + 1, kv].T / np.sqrt(hd)
            pr = np.exp(s - s.max())
            out[i, h] = (pr / pr.sum()) @ v[lo:i + 1, kv]
    return out.reshape(t, H * hd) @ w["wo"]


@pytest.mark.parametrize("attention", ["dense", "flash"])
@pytest.mark.parametrize("layer", [0, 1])
def test_attention_by_layer_kind_against_a_numpy_loop(attention, layer):
    """Layer 0: every key up to the query's, no positions. Layer 1: the
    last 8 keys with the query's own, rotated. One key/value head serves
    two query heads in order."""
    cfg, model, params, tokens = _setup(attention=attention,
                                        flash_blocks=(16, 16))
    u = jax.random.normal(jax.random.PRNGKey(3), (1, T, cfg.d_model))
    p = params[f"h{layer}"]["attn"]
    got = st.Attention(cfg, layer).apply({"params": p}, u)[0]
    want = _attention_by_hand(u[0], p, cfg, rotated=bool(layer),
                              window=cfg.sliding_window if layer else None)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)
    # the other kind is another result
    other = _attention_by_hand(u[0], p, cfg, rotated=not layer,
                               window=None if layer else cfg.sliding_window)
    assert np.abs(np.asarray(got) - other).max() > 1e-3


def test_the_layouts_say_each_layers_kind():
    """A window layer sees nothing further back than its window; the global
    layer does; positions enter where ``rope_layout`` says and nowhere
    else."""
    cfg, model, params, tokens = _setup()
    u = jax.random.normal(jax.random.PRNGKey(3), (1, T, cfg.d_model))
    far = u.at[0, 0].add(1.0)           # position 0, seen from position 31?
    for layer, reaches in ((0, True), (1, False)):
        attn = st.Attention(cfg, layer)
        p = params[f"h{layer}"]["attn"]
        moved = (attn.apply({"params": p}, far)
                 - attn.apply({"params": p}, u))[0]
        assert np.abs(np.asarray(moved[:8])).max() > 1e-4
        assert (np.abs(np.asarray(moved[8:])).max() > 1e-6) == reaches
    # a row shifted by one token: without positions every later output is
    # the earlier one's, shifted (up to the window's reach); with RoPE too,
    # because RoPE is relative: so what tells them apart is the kind of mask
    # and test_attention_by_layer_kind's loop. Here: the layouts are read.
    for name in ("sliding_window_layout", "rope_layout"):
        flipped = dataclasses.replace(cfg, **{name: (1, 0, 0, 0)})
        assert float(st.loss_fn(st.SmallThinker(flipped), params, tokens)
                     ) != float(st.loss_fn(model, params, tokens))


def test_the_router_reads_the_blocks_input():
    """The choices of block ``i`` are the top-k of ``softmax(x W_r)`` for
    the stream ``x`` that enters the block, un-normed; of the normed stream
    after attention they are not."""
    cfg, model, params, tokens = _setup()
    _, kept = model.apply(
        {"params": params}, tokens, mutable=["intermediates"],
        capture_intermediates=lambda m, _: isinstance(m, st.Block)
        or m.name == "norm_post")
    kept = kept["intermediates"]
    x = params["wte"][tokens]
    for i in range(cfg.num_layers):
        block = kept[f"h{i}"]
        router = params[f"h{i}"]["moe"]["router"]
        pick = lambda read: np.asarray(jax.lax.top_k(jax.nn.softmax(
            read.reshape(-1, cfg.d_model) @ router, -1), cfg.top_k)[1])
        np.testing.assert_array_equal(
            np.asarray(block["moe"]["choice"][0]), pick(x))
        after = block["norm_post"]["__call__"][0]
        assert (pick(after) != pick(x)).any()
        x = block["__call__"][0]


# ---------------------------------------------------------------------------
# the config, the zoo, serving
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("changes,match", [
    (dict(sliding_window_layout=(0, 1)), "names 2 layers"),
    (dict(rope_layout=(0, 1, 1)), "names 3 layers"),
    (dict(num_kv_heads=3), "must divide")])
def test_the_model_refuses_what_it_cannot_build(changes, match):
    model = st.SmallThinker(st.SmallThinkerConfig.tiny(**changes))
    with pytest.raises(ValueError, match=match):
        model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))


def test_the_published_defaults():
    cfg = st.SmallThinkerConfig()
    assert len(cfg.sliding_window_layout) == cfg.num_layers == 52
    assert cfg.sliding_window_layout == cfg.rope_layout
    assert cfg.rope_layout[:5] == (0, 1, 1, 1, 0)
    assert sum(cfg.rope_layout) == 39
    assert (cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.d_expert, cfg.experts_total, cfg.top_k, cfg.sliding_window,
            cfg.vocab_size, cfg.rope_theta, cfg.rms_eps) == (
                2560, 28, 4, 128, 768, 64, 6, 4096, 151936, 1.5e6, 1e-6)
    from horovod_tpu import models
    assert models.SmallThinker is st.SmallThinker
    assert models.SmallThinkerConfig is st.SmallThinkerConfig


def test_serving_refuses_the_family_and_says_why():
    from horovod_tpu.models import generate as gen
    cfg = st.SmallThinkerConfig.tiny()
    with pytest.raises(TypeError,
                       match="trained here and not served.*window"):
        gen.decode_family(cfg)
    with pytest.raises(TypeError, match="trained here and not served"):
        gen.generate(st.SmallThinker(cfg), {}, jnp.zeros((1, 4), jnp.int32),
                     2)


# ---------------------------------------------------------------------------
# the shares of an expert-parallel deployment
# ---------------------------------------------------------------------------

def _layer(n=48, d=32, f=16, experts=64, seed=0):
    """A layer at the published router width and top-6: the rows the experts
    read, the stream the router reads, and all 64 experts."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    draw = lambda k, *shape: jax.random.normal(k, shape, jnp.float32)
    return (draw(ks[0], n, d), draw(ks[1], n, d), draw(ks[2], d, experts),
            draw(ks[3], experts, d, f) / 4, draw(ks[4], experts, d, f) / 4,
            draw(ks[5], experts, f, d) / 4)


def _uncut(m, x, router, w_gate, w_up, w_down, first=0):
    """The whole layer by the reference: every expert held."""
    p = {"w_gate": w_gate, "w_up": w_up, "w_down": w_down}
    with jax.default_matmul_precision("highest"):
        gate, choice = ref.route(x, router, top_k=6, norm_topk=True)
        return ref._experts(m, gate, choice, p, experts_first=first,
                            act="relu")


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """One chip's share of an 8-way expert-parallel deployment, eight
    times: with the route made from a second stream and ReGLU experts, the
    shares of all eight holders add up to the uncut reference's whole
    layer, and every assignment has its row on exactly one of them."""
    m, x, router, w_gate, w_up, w_down = _layer()
    total, given = jnp.zeros_like(m), 0
    for first in range(0, 64, 8):
        held = slice(first, first + 8)
        out, aux = moe.routed_share(
            m, router, w_gate[held], w_up[held], w_down[held], first=first,
            top_k=6, dtype=jnp.float32, route_from=x, act="relu")
        share = _uncut(m, x, router, w_gate[held], w_up[held], w_down[held],
                       first=first)
        np.testing.assert_allclose(np.asarray(out), np.asarray(share),
                                   atol=1e-5)
        total = total + out
        given += int(aux["group_sizes"].sum())
    assert given == m.shape[0] * 6              # every assignment, once
    np.testing.assert_allclose(
        np.asarray(total),
        np.asarray(_uncut(m, x, router, w_gate, w_up, w_down)), atol=1e-5)
    # fed the rows themselves, or silu, the layer is another
    for other in (dict(route_from=m, act="relu"),
                  dict(route_from=x, act="silu")):
        out, _ = moe.routed_share(m, router, w_gate, w_up, w_down, first=0,
                                  top_k=6, dtype=jnp.float32, **other)
        assert np.abs(np.asarray(out) - np.asarray(total)).max() > 1e-3


def test_under_an_ep_axis_of_four_the_block_is_the_uncut_block():
    """``ep_axis`` of 4 on CPU devices: positions and experts sharded, both
    streams gathered, the shares reduce-scattered: the same as every expert
    held on one device."""
    cfg, model, params, tokens = _setup(experts_held=(0, 8))
    x = jax.random.normal(jax.random.PRNGKey(4), (4, 8, cfg.d_model))
    p = params["h1"]
    whole = st.Block(cfg, 1).apply({"params": p}, x)
    mesh = Mesh(np.array(jax.devices()[:4]), ("ep",))
    peer = st.Block(dataclasses.replace(cfg, experts_held=(0, 2),
                                        ep_axis="ep"), 1)
    experts = ("w_gate", "w_up", "w_down")
    specs = jax.tree_util.tree_map_with_path(
        lambda path, _: P("ep") if path[-1].key in experts else P(), p)
    got = jax.jit(jax.shard_map(
        lambda p, x: peer.apply({"params": p}, x), mesh=mesh,
        in_specs=(specs, P("ep")), out_specs=P("ep")))(p, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(whole), atol=2e-5)


# ---------------------------------------------------------------------------
# names and gauges
# ---------------------------------------------------------------------------

def _gauge(name, program):
    return [s["value"] for s in hvd.metrics.snapshot()["gauges"].get(name, ())
            if s["labels"].get("program") == program]


def test_the_routing_manifest_of_a_traced_step():
    from horovod_tpu.ops.flash_attention import causal_tiles, window_tiles
    cfg, model, params, tokens = _setup(attention="flash",
                                        flash_blocks=(8, 8))
    with tracing.program("smallthinker_step"):
        jax.make_jaxpr(jax.grad(lambda p: st.loss_fn(model, p, tokens)))(
            params)
    seen, of = window_tiles(T, cfg.sliding_window, 8, 8)
    visited, total = causal_tiles(T, 8, 8)
    n = 2 * T
    want = {"moe_rows_bound": n * 2,
            "moe_rows_tight": moe.row_bounds(n, 2, 4, 8)[0],
            "window_tiles_visited": seen, "window_tiles_total": of,
            "causal_tiles_visited": visited, "causal_tiles_total": total,
            "flash_bwd_kernels": 2}
    for name, value in want.items():
        assert _gauge(name, "smallthinker_step") == [value], name
    assert seen < visited < total == of


def test_routing_load_from_the_auxiliary_output():
    cfg, model, params, tokens = _setup()
    _, kept = model.apply({"params": params}, tokens,
                          mutable=["intermediates"])
    assert sorted(kept["intermediates"]) == ["h0", "h1", "h2", "h3"]
    sizes = np.stack([np.asarray(kept["intermediates"][f"h{i}"]["moe"][
        "group_sizes"][0]) for i in range(4)])
    tracing.routing_load("smallthinker_look", sizes)
    assert _gauge("moe_local_assignments", "smallthinker_look") == [
        pytest.approx(sizes.sum(1).mean())]


# ---------------------------------------------------------------------------
# the accepted families' steps are left as they were
# ---------------------------------------------------------------------------

def _tiny_steps():
    """``name -> (loss function of the parameters, parameters)`` of the four
    accepted families at their tiny sizes with flash attention."""
    from horovod_tpu.models import glm4_moe_lite as glm, lfm2, sdar
    from horovod_tpu.models.gpt2 import GPT2, GPT2Config
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, T), 0, 256)
    steps = {}
    cfg = GPT2Config.tiny(attention="flash", dtype=jnp.float32)
    model = GPT2(cfg)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    steps["gpt2"] = (lambda p, m=model: jnp.mean(
        m.apply({"params": p}, tokens)), params)
    cfg = lfm2.LFM2Config.tiny(attention="flash", dtype=jnp.float32)
    model = lfm2.LFM2(cfg)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    steps["lfm2"] = (lambda p, m=model: lfm2.loss_fn(m, p, tokens), params)
    cfg = glm.Glm4MoeLiteConfig.tiny(attention="flash", dtype=jnp.float32)
    model = glm.Glm4MoeLite(cfg)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    steps["glm4"] = (lambda p, m=model: glm.loss_fn(m, p, tokens), params)
    cfg = sdar.SDARConfig.tiny(attention="flash", dtype=jnp.float32)
    model = sdar.SDAR(cfg)
    params = model.init(jax.random.PRNGKey(0), tokens, tokens)["params"]
    steps["sdar"] = (lambda p, m=model: jnp.mean(
        m.apply({"params": p}, tokens, tokens)), params)
    return steps


def test_the_accepted_families_steps_hold_nothing_of_this_one():
    """The tiny steps of the four accepted families, forward and gradient,
    lowered as for the chip: no band's comparison, no second stream, no
    relu among the experts, the kernels they had (their jaxprs are the
    parent's, compared once against a checkout of it: CHANGES.md)."""
    import importlib
    fa = importlib.import_module("horovod_tpu.ops.flash_attention")
    seen, steps = {}, _tiny_steps()
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(fa, "_use_interpret", lambda: False)
        for name, (loss, params) in steps.items():
            with tracing.program("accepted_" + name):
                text = str(jax.make_jaxpr(jax.grad(loss))(params))
            assert "name=relu" not in text, name
            assert _gauge("window_tiles_visited", "accepted_" + name) == []
            seen[name] = text.count("name=flash_fwd")
    assert all(seen.values()), seen
