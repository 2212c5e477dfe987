"""The block-diffusion decoder with routed experts (``models/sdar.py``,
``ops/moe.RoutedExperts``, the block-diffusion mask of the flash kernels), on
the CPU at a small size with the published ratios: system against the plain
reference of the benchmark on seeded weights, the shares of an expert layer
adding up to the uncut layer, the flash kernels (interpret mode) against the
dense mask, the tile skip, and that no path drops a token."""

import importlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from horovod_tpu import tracing
from horovod_tpu.models import sdar
from horovod_tpu.ops import moe
from horovod_tpu.ops.attention import (block_diffusion_mask,
                                       multihead_attention)

fa = importlib.import_module("horovod_tpu.ops.flash_attention")
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark"))
from reference import sdar_moe_ref as ref  # noqa: E402

T, L = 32, 4


def _kw(cfg):
    return dict(num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
                eps=cfg.rms_eps, rope_theta=cfg.rope_theta, top_k=cfg.top_k,
                norm_topk=cfg.norm_topk, experts_first=cfg.experts_held[0],
                block_len=cfg.block_len, t_min=cfg.t_min,
                mask_id=cfg.mask_token)


def _setup(**kw):
    """8 experts of which 2 held, block length 4, T 32, fp32."""
    cfg = sdar.SDARConfig.tiny(experts_held=(2, 2), top_k=4,
                               dtype=jnp.float32, **kw)
    model = sdar.SDAR(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, T), 0,
                                cfg.vocab_size)
    params = model.init(jax.random.PRNGKey(0), tokens, tokens)["params"]
    return cfg, model, params, tokens


# ---------------------------------------------------------------------------
# system against the reference
# ---------------------------------------------------------------------------

def test_the_programs_noise_is_the_references():
    tokens = jax.random.randint(jax.random.PRNGKey(3), (3, T), 0, 250)
    mine = sdar.block_noise(ref.row_keys(tokens), T, L, 1e-3)
    theirs = ref.noise(tokens, L, 1e-3)
    for a, b in zip(mine, theirs):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    levels, masked = mine
    assert levels.shape == (3, T // L) and masked.shape == (3, T)
    assert float(levels.min()) >= 1e-3 and float(levels.max()) <= 1.0
    # rows differ, and a row's noise follows from its tokens alone
    again = sdar.block_noise(ref.row_keys(tokens[1:2]), T, L, 1e-3)
    np.testing.assert_array_equal(np.asarray(again[1][0]),
                                  np.asarray(masked[1]))
    assert not np.array_equal(np.asarray(masked[0]), np.asarray(masked[1]))


@pytest.mark.parametrize("attention,remat", [
    ("dense", False), ("flash", False), ("flash", True)])
def test_loss_and_gradients_match_the_reference(attention, remat):
    cfg, model, params, tokens = _setup(attention=attention, remat=remat,
                                        flash_blocks=(16, 32))
    noise = sdar.block_noise(ref.row_keys(tokens), T, L, cfg.t_min)
    loss, grads = jax.value_and_grad(
        lambda p: sdar.loss_fn(model, p, tokens, noise))(params)
    tree = ref.from_system(params, cfg.num_layers)
    want, want_grads = jax.value_and_grad(
        lambda r: ref.loss(r, tokens, **_kw(cfg)))(tree)
    np.testing.assert_allclose(float(loss), float(want), rtol=1e-5)
    got = ref.from_system(grads, cfg.num_layers)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree_util.tree_leaves(want_grads)):
        scale = float(jnp.max(jnp.abs(b)))
        assert scale > 0, path
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-5 * scale, err_msg=str(path))


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


def test_the_mask_id_defaults_to_the_last_row_held():
    assert sdar.SDARConfig.tiny().mask_token == 255
    assert sdar.SDARConfig.tiny(mask_id=7).mask_token == 7


def test_rows_must_be_whole_blocks():
    cfg, model, params, _ = _setup()
    bad = jnp.zeros((1, T + 2), jnp.int32)
    with pytest.raises(ValueError, match="whole blocks"):
        model.apply({"params": params}, bad, bad)


# ---------------------------------------------------------------------------
# the expert layer: shares, droplessness, the exchange
# ---------------------------------------------------------------------------

def _layer(n=64, d=32, f=16, experts=8, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (n, d), jnp.float32)
    router = jax.random.normal(ks[1], (d, experts), jnp.float32) * 0.5
    w_gate = jax.random.normal(ks[2], (experts, d, f), jnp.float32) * 0.2
    w_up = jax.random.normal(ks[3], (experts, d, f), jnp.float32) * 0.2
    w_down = jax.random.normal(ks[4], (experts, f, d), jnp.float32) * 0.2
    return x, router, w_gate, w_up, w_down


def _uncut(x, router, w_gate, w_up, w_down, top_k):
    """The whole layer by the reference: every expert held."""
    p = {"router": router, "w_gate": w_gate, "w_up": w_up, "w_down": w_down}
    with jax.default_matmul_precision("highest"):
        return ref._experts(x, p, top_k=top_k, norm_topk=True,
                            experts_first=0)[0]


@pytest.mark.parametrize("top_k", [2, 4])
def test_the_four_shares_add_up_to_the_uncut_layer(top_k):
    x, router, w_gate, w_up, w_down = _layer()
    total = jnp.zeros_like(x)
    given = 0
    for first in range(0, 8, 2):
        held = slice(first, first + 2)
        out, aux = moe.routed_share(
            x, router, w_gate[held], w_up[held], w_down[held], first=first,
            top_k=top_k, dtype=jnp.float32)
        share = _uncut(x, router,
                       *(jnp.where((jnp.arange(8) // 2 == first // 2
                                    )[:, None, None], w, 0)
                         for w in (w_gate, w_up, w_down)), top_k)
        np.testing.assert_allclose(np.asarray(out), np.asarray(share),
                                   atol=1e-5)
        total = total + out
        given += int(aux["group_sizes"].sum())
    assert given == x.shape[0] * top_k          # every assignment, once
    np.testing.assert_allclose(
        np.asarray(total),
        np.asarray(_uncut(x, router, w_gate, w_up, w_down, top_k)),
        atol=1e-5)


def test_no_token_is_dropped_when_every_position_chooses_one_expert():
    x, router, w_gate, w_up, w_down = _layer()
    x = jnp.abs(x)                               # so that a column can win
    router = router.at[:, 3].set(5.0)            # everything to expert 3
    out, aux = moe.routed_share(x, router, w_gate[2:4], w_up[2:4],
                                w_down[2:4], first=2, top_k=2,
                                dtype=jnp.float32)
    assert int(aux["group_sizes"][1]) == x.shape[0]
    assert bool((aux["choice"] == 3).any(-1).all())
    want = _uncut(x, router, *(jnp.where((jnp.arange(8) // 2 == 1
                                          )[:, None, None], w, 0)
                               for w in (w_gate, w_up, w_down)), 2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5)
    # and the capacity router beside it would have dropped most of them
    dispatch, _, _ = moe.Top1Router(8).apply(
        {"params": {"router": router}}, x)
    assert float(dispatch.sum()) < x.shape[0]


def test_gradients_of_the_share_are_the_references():
    x, router, w_gate, w_up, w_down = _layer(n=32)
    held = slice(4, 6)

    def mine(x, router, w_gate, w_up, w_down):
        out, _ = moe.routed_share(x, router, w_gate, w_up, w_down, first=4,
                                  top_k=4, dtype=jnp.float32)
        return jnp.sum(out * jnp.cos(out))

    def theirs(x, router, w_gate, w_up, w_down):
        p = {"router": router, "w_gate": w_gate, "w_up": w_up,
             "w_down": w_down}
        with jax.default_matmul_precision("highest"):
            out = ref._experts(x, p, top_k=4, norm_topk=True,
                               experts_first=4)[0]
        return jnp.sum(out * jnp.cos(out))

    args = (x, router, w_gate[held], w_up[held], w_down[held])
    got = jax.grad(mine, argnums=range(5))(*args)
    want = jax.grad(theirs, argnums=range(5))(*args)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


# A holder of 2 of 16 experts under top-2 of 1,024 positions: 256 rows
# expected, windows of 512, 2,048 at worst.
CUT = dict(n=1024, experts=16)
HELD, TOP_K = slice(4, 6), 2
TIGHT, ROWS = 512, 2048
# rows a routing gives the two held experts, by what it is
WINDOWS = {"one window": 212, "two windows": 780, "the worst case": ROWS}


def _routing(kind):
    """The cut layer's inputs under a router that spreads the positions
    over all 16 experts, one that favours the first held expert (its rows
    lie across two windows, the second's in the second), and one that
    sends every position to the two held."""
    x, router, w_gate, w_up, w_down = _layer(**CUT)
    if kind == "two windows":
        x, router = x + 0.5, router.at[:, HELD.start].set(0.3)
    if kind == "the worst case":
        x, router = jnp.abs(x), router.at[:, HELD].set(5.0)
    return x, router, w_gate[HELD], w_up[HELD], w_down[HELD]


def _cut_share(*args):
    return moe.routed_share(*args, first=HELD.start, top_k=TOP_K,
                            dtype=jnp.float32)


def _cut_loss(*args):
    out, aux = _cut_share(*args)
    return jnp.sum(out * jnp.cos(out)), (out, aux["group_sizes"])


def _cut_grad(*args):
    (_, (out, sizes)), grads = jax.value_and_grad(
        _cut_loss, argnums=range(5), has_aux=True)(*args)
    return out, sizes, grads


def _cut_reference(x, router, w_gate, w_up, w_down):
    p = {"router": router, "w_gate": w_gate, "w_up": w_up, "w_down": w_down}
    with jax.default_matmul_precision("highest"):
        out = ref._experts(x, p, top_k=TOP_K, norm_topk=True,
                           experts_first=HELD.start)[0]
    return jnp.sum(out * jnp.cos(out)), out


def test_the_row_bounds_follow_what_a_share_can_expect():
    assert moe.row_bounds(CUT["n"], TOP_K, 2, 16) == (TIGHT, ROWS)
    # the benchmark's cells: a quarter of the worst case
    assert moe.row_bounds(16384, 8, 16, 128) == (32768, 131072)
    assert moe.row_bounds(32768, 4, 8, 64) == (32768, 131072)
    assert moe.row_bounds(16384, 4, 8, 64) == (16384, 65536)
    # a holder of every expert, and shares under one tile of rows
    assert moe.row_bounds(64, 2, 8, 8) == (128, 128)
    assert moe.row_bounds(128, 4, 2, 8) == (256, 256)
    assert moe.row_bounds(4096, 4, 2, 8) == (8192, 8192)    # held < top_k


def _is_the_references(args, given):
    """Outputs and every gradient of the cut share against the dense
    reference, on a routing that gives the held experts ``given`` rows."""
    out, sizes, got = _cut_grad(*args)
    (_, want_out), want = jax.value_and_grad(
        _cut_reference, argnums=range(5), has_aux=True)(*args)
    assert int(sizes.sum()) == given
    np.testing.assert_allclose(np.asarray(out), np.asarray(want_out),
                               atol=2e-5)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-4)


@pytest.mark.parametrize("kind", list(WINDOWS))
def test_the_share_over_its_windows_is_the_references(kind):
    """Whether the routing fits one window of 512 rows or needs all four:
    none of 2,048 assignments is dropped."""
    _is_the_references(_routing(kind), WINDOWS[kind])


def test_a_worst_case_that_is_no_whole_number_of_windows():
    """1,000 positions: 2,000 rows at worst, the last of four windows
    partly past the end of the assignments."""
    x, router, w_gate, w_up, w_down = _layer(n=1000, experts=16)
    assert moe.row_bounds(1000, TOP_K, 2, 16) == (TIGHT, 2000)
    _is_the_references((jnp.abs(x), router.at[:, HELD].set(5.0),
                        w_gate[HELD], w_up[HELD], w_down[HELD]), 2000)


@pytest.mark.parametrize("kind", list(WINDOWS))
def test_the_windows_agree_with_one_shape_for_the_worst_case(kind,
                                                              monkeypatch):
    """The same routing through windows of 512 rows and through one shape
    of 2,048, which holds any routing and has no loop."""
    args = _routing(kind)
    out, _, grads = _cut_grad(*args)
    monkeypatch.setattr(moe, "row_bounds", lambda *a: (ROWS, ROWS))
    whole_out, _, whole = _cut_grad(*args)
    np.testing.assert_allclose(np.asarray(out), np.asarray(whole_out),
                               rtol=1e-5, atol=2e-6)
    for a, b in zip(grads, whole):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=2e-5)


@pytest.mark.parametrize("kind", ["one window", "two windows"])
def test_what_the_grouped_products_leave_past_their_groups_is_not_read(
        kind, monkeypatch):
    """Nothing promises what a grouped kernel writes in the rows past its
    groups: with NaN there, outputs and gradients are what they were."""
    args = _routing(kind)
    out, _, grads = _cut_grad(*args)
    ffn, traced = moe._ffn, []

    def poisoned(xs, w_gate, w_up, w_down, sizes, act="silu"):
        traced.append(xs.shape)
        past = jnp.arange(xs.shape[0])[:, None] >= jnp.sum(sizes)
        return jnp.where(past, jnp.nan,
                         ffn(xs, w_gate, w_up, w_down, sizes, act))

    monkeypatch.setattr(moe, "_ffn", poisoned)
    again_out, _, again = _cut_grad(*args)
    assert traced == [(TIGHT, 32)] * 2          # forward, and to go back
    for a, b in zip((out, *grads), (again_out, *again)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _shapes(jaxpr):
    """Shapes of every array in ``jaxpr`` and in the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        for var in eqn.invars + eqn.outvars:
            yield tuple(getattr(var.aval, "shape", ()))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _shapes(sub)


def _wide(jaxpr, d=32, f=16):
    return sorted({s for s in _shapes(jaxpr) if len(s) == 2
                   and s[0] == CUT["n"] * TOP_K and s[1] in (d, f)})


def _traced(what, share, *args):
    """The jaxpr of ``share``, or of its gradient in every argument."""
    fn = (lambda *a: share(*a)[0]) if what == "forward" else jax.grad(
        lambda *a: jnp.sum(share(*a)[0]), argnums=range(5))
    return jax.make_jaxpr(fn)(*args).jaxpr


@pytest.mark.parametrize("what", ["forward", "gradient"])
def test_nothing_is_shaped_by_positions_x_top_k(what, monkeypatch):
    args = _routing("one window")
    jaxpr = _traced(what, _cut_share, *args)
    # a loop over the windows, and one to go back over them
    assert str(jaxpr).count("while[") == (1 if what == "forward" else 2)
    assert "cond[" not in str(jaxpr)
    assert _wide(jaxpr) == []
    # the detector sees one shape for the worst case, which is so shaped
    monkeypatch.setattr(moe, "row_bounds", lambda *a: (ROWS, ROWS))
    assert _wide(_traced(what, _cut_share, *args)) == [(ROWS, 16),
                                                       (ROWS, 32)]


@pytest.mark.parametrize("what", ["forward", "gradient"])
def test_a_holder_of_every_expert_has_one_window_and_no_loop(what):
    share = lambda *a: moe.routed_share(*a, first=0, top_k=2,
                                        dtype=jnp.float32)
    text = str(_traced(what, share, *_layer()))
    assert "while[" not in text and "cond[" not in text


def test_under_an_ep_axis_the_shares_are_exchanged():
    """Four peers, positions and experts sharded over ``ep``: every peer
    ends with the whole layer's result for its own positions."""
    x, router, w_gate, w_up, w_down = _layer()
    layer = moe.RoutedExperts(8, (0, 2), 2, 16, dtype=jnp.float32,
                              ep_axis="ep")
    mesh = Mesh(np.array(jax.devices()[:4]), ("ep",))

    def run(x, router, w_gate, w_up, w_down):
        params = {"router": router, "w_gate": w_gate, "w_up": w_up,
                  "w_down": w_down}
        return layer.apply({"params": params}, x[None])[0]

    out = jax.jit(jax.shard_map(
        run, mesh=mesh, in_specs=(P("ep"), P(), P("ep"), P("ep"), P("ep")),
        out_specs=P("ep")))(x, router, w_gate, w_up, w_down)
    np.testing.assert_allclose(
        np.asarray(out),
        np.asarray(_uncut(x, router, w_gate, w_up, w_down, 2)), atol=1e-5)


def test_under_an_ep_axis_each_peer_loops_by_its_own_rows():
    """Four peers of 4 experts each, every position sent to two experts of
    peer 1: it goes over two windows of 1,024 rows while the other three,
    given nothing, go over none; the exchanged result is the uncut
    layer."""
    x, router, w_gate, w_up, w_down = _layer(**CUT)
    x, router = jnp.abs(x), router.at[:, 4:6].set(5.0)
    assert moe.row_bounds(CUT["n"], TOP_K, 4, 16) == (1024, 2048)
    layer = moe.RoutedExperts(16, (0, 4), TOP_K, 16, dtype=jnp.float32,
                              ep_axis="ep")
    mesh = Mesh(np.array(jax.devices()[:4]), ("ep",))

    def run(x, router, w_gate, w_up, w_down):
        params = {"router": router, "w_gate": w_gate, "w_up": w_up,
                  "w_down": w_down}
        out, kept = layer.apply({"params": params}, x[None],
                                mutable=["intermediates"])
        return out[0], kept["intermediates"]["group_sizes"][0]

    out, sizes = jax.jit(jax.shard_map(
        run, mesh=mesh, in_specs=(P("ep"), P(), P("ep"), P("ep"), P("ep")),
        out_specs=(P("ep"), P("ep"))))(x, router, w_gate, w_up, w_down)
    assert np.asarray(sizes).reshape(4, 4).sum(1).tolist() == [0, 2048, 0, 0]
    np.testing.assert_allclose(
        np.asarray(out),
        np.asarray(_uncut(x, router, w_gate, w_up, w_down, TOP_K)),
        atol=2e-5)


def test_the_layer_refuses_experts_it_cannot_hold():
    layer = moe.RoutedExperts(8, (6, 4), 2, 16)
    with pytest.raises(ValueError, match="no range"):
        layer.init(jax.random.PRNGKey(0), jnp.zeros((1, 4, 8)))


# ---------------------------------------------------------------------------
# the block-diffusion mask in the flash kernels
# ---------------------------------------------------------------------------

def test_the_mask_is_the_published_one():
    pos = jnp.arange(2 * T, dtype=jnp.int32)
    mine = np.asarray(block_diffusion_mask(pos[:, None], pos[None, :], T, L))
    np.testing.assert_array_equal(mine, np.asarray(ref.visible(T, L)))
    assert mine.sum() == T * (T + L)             # visible pairs a row
    assert not mine[T:, :T].any()                # clean never sees noisy
    odd = np.asarray(block_diffusion_mask(pos[:60, None], pos[None, :60],
                                          30, 3))
    np.testing.assert_array_equal(odd, np.asarray(ref.visible(30, 3)))


def _qkv(seq, heads=2, d=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    mk = lambda k: jax.random.normal(k, (2, 2 * seq, heads, d), jnp.float32)
    return mk(ks[0]), mk(ks[1]), mk(ks[2]), mk(ks[3])


@pytest.mark.parametrize("seq,blocks", [
    (40, (16, 32)),      # 80 positions: no multiple of either tile
    (40, (32, 16)), (32, (16, 16)), (36, (128, 128))])
def test_flash_under_the_mask_matches_dense_forward_and_gradients(seq, blocks):
    q, k, v, do = _qkv(seq)

    def run(impl):
        f = lambda q, k, v: jnp.sum(do * multihead_attention(
            q, k, v, impl=impl, causal=False, block_diffusion=(seq, L),
            flash_blocks=blocks))
        return (multihead_attention(q, k, v, impl=impl, causal=False,
                                    block_diffusion=(seq, L),
                                    flash_blocks=blocks),
                *jax.grad(f, argnums=(0, 1, 2))(q, k, v))

    for a, b in zip(run("flash"), run("dense")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


@pytest.mark.parametrize("seq,blk,bq,bk", [
    (32, 4, 16, 16), (40, 4, 16, 32), (40, 4, 32, 16), (48, 8, 16, 64),
    (36, 3, 8, 16), (64, 4, 128, 128)])
def test_the_tile_skip_visits_exactly_the_tiles_with_a_visible_pair(
        seq, blk, bq, bk):
    mask = np.asarray(ref.visible(seq, blk))
    bq_, bk_ = min(bq, 2 * seq), min(bk, 2 * seq)
    nq, nk = -(-2 * seq // bq_), -(-2 * seq // bk_)
    want = np.array([[mask[i * bq_:(i + 1) * bq_,
                           j * bk_:(j + 1) * bk_].any()
                      for j in range(nk)] for i in range(nq)])
    got = fa._bd_skip(np.arange(nq)[:, None], np.arange(nk)[None, :], bq_,
                      bk_, seq, blk, xp=np)
    np.testing.assert_array_equal(got, want)
    assert fa.bd_tiles(seq, blk, bq, bk) == (int(want.sum()), nq * nk)
    # and inside a kernel the same function decides, on traced scalars
    traced = jax.jit(lambda i, j: fa._bd_skip(i, j, bq_, bk_, seq, blk))
    for i in range(nq):
        for j in range(nk):
            assert bool(traced(i, j)) == bool(want[i, j]), (i, j)


def test_at_the_cells_size_a_third_of_the_tiles_is_visited():
    visited, total = fa.bd_tiles(4096, 4, 256, 512)
    assert total == 32 * 16
    assert 0.25 < visited / total < 0.35


def test_block_diffusion_refuses_what_it_cannot_mask():
    q, k, v, _ = _qkv(32)
    with pytest.raises(ValueError, match="block_diffusion"):
        fa.flash_attention(q, k, v, causal=True, block_diffusion=(32, 4))
    with pytest.raises(ValueError, match="block_diffusion"):
        fa.flash_attention(q, k, v, block_diffusion=(30, 4))
    with pytest.raises(ValueError, match="positions"):
        multihead_attention(q, k, v, impl="dense", causal=False,
                            block_diffusion=(16, 4))


def test_the_causal_path_lowers_as_before():
    """The mask's generalisation leaves the causal kernels their text: no
    block-diffusion arithmetic in a causal call's jaxpr."""
    q, k, v, _ = _qkv(32)
    causal = str(jax.make_jaxpr(lambda q, k, v: fa.flash_attention(
        q, k, v, causal=True, block_q=16, block_k=16))(q, k, v))
    bd = str(jax.make_jaxpr(lambda q, k, v: fa.flash_attention(
        q, k, v, block_diffusion=(32, 4), block_q=16, block_k=16))(q, k, v))
    assert "shift_right_logical" in bd
    assert "shift_right_logical" not in causal


# ---------------------------------------------------------------------------
# the routing manifest
# ---------------------------------------------------------------------------

def _gauge(name, program):
    import horovod_tpu as hvd
    return [s["value"] for s in hvd.metrics.snapshot()["gauges"].get(name, ())
            if s["labels"].get("program") == program]


def test_the_routing_manifest_of_a_traced_step():
    cfg, model, params, tokens = _setup(attention="flash",
                                        flash_blocks=(16, 32))
    noise = sdar.block_noise(ref.row_keys(tokens), T, L, cfg.t_min)
    with tracing.program("sdar_step"):
        jax.make_jaxpr(lambda p: sdar.loss_fn(model, p, tokens, noise))(
            params)
    visited, total = fa.bd_tiles(T, L, 16, 32)
    # 128 positions, 2 of 8 experts held under top-4: 256 rows at worst,
    # and twice the 128 expected is no less
    want = {"moe_rows_bound": 2 * 2 * T * 2, "moe_rows_tight": 2 * 2 * T * 2,
            "bd_tiles_visited": visited, "bd_tiles_total": total}
    for name, value in want.items():
        assert _gauge(name, "sdar_step") == [value], name
    with pytest.raises(ValueError, match="routing manifest"):
        tracing.note_routing(moe_capacity=3)
    tracing.note_routing(moe_rows_bound=2)       # outside a program: nothing
    assert _gauge("moe_rows_bound", "sdar_step") == [2 * 2 * T * 2]


def test_routing_load_from_the_auxiliary_output():
    cfg, model, params, tokens = _setup()
    _, kept = model.apply({"params": params}, tokens, tokens,
                          mutable=["intermediates"])
    sizes = np.stack([np.asarray(
        kept["intermediates"][f"h{i}"]["moe"]["group_sizes"][0])
        for i in range(cfg.num_layers)])
    choice = np.asarray(kept["intermediates"]["h0"]["moe"]["choice"][0])
    assert sizes.shape == (cfg.num_layers, 2)
    assert sizes[0].tolist() == [int((choice == e).sum()) for e in (2, 3)]
    tracing.routing_load("sdar_look", sizes)
    assert _gauge("moe_local_assignments", "sdar_look") == [
        pytest.approx(sizes.sum(1).mean())]
    assert _gauge("moe_load_max_over_mean", "sdar_look") == [
        pytest.approx(sizes.max() / sizes.mean())]
    # no program of that name said what its share is shaped for
    assert _gauge("moe_rows_overflow_layers", "sdar_look") == []
    with tracing.program("sdar_look"):
        jax.make_jaxpr(lambda p: model.apply({"params": p}, tokens, tokens))(
            params)
    tight = _gauge("moe_rows_tight", "sdar_look")[0]
    assert tight == 2 * 2 * T * 2 and sizes.sum(1).max() <= tight
    tracing.routing_load("sdar_look", sizes)
    assert _gauge("moe_rows_overflow_layers", "sdar_look") == [0]
    # one more layer, a row over the bound: it would go over two windows
    over = np.concatenate([sizes, [[tight, 1]]])
    tracing.routing_load("sdar_look", over)
    assert _gauge("moe_rows_overflow_layers", "sdar_look") == [1]
    assert _gauge("moe_local_assignments", "sdar_look") == [
        pytest.approx(over.sum(1).mean())]
