"""Flash attention (pallas kernel, interpret mode on CPU) == dense attention
(SURVEY §4; kernels run the same code path Mosaic compiles on TPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import flash_attention


def dense_attention(q, k, v, causal, scale=None):
    d = q.shape[-1]
    scale = d ** -0.5 if scale is None else scale
    logits = np.einsum("bqhd,bkhd->bhqk", q, k).astype(np.float64) * scale
    if causal:
        t = q.shape[1]
        mask = np.tril(np.ones((t, t), bool))
        logits = np.where(mask[None, None], logits, -1e30)
    logits = logits - logits.max(axis=-1, keepdims=True)
    p = np.exp(logits)
    p = p / p.sum(axis=-1, keepdims=True)
    return np.einsum("bhqk,bkhd->bqhd", p, v)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_dense(rng, causal):
    B, T, H, D = 2, 64, 2, 16
    q, k, v = (rng.standard_normal((B, T, H, D)).astype(np.float32)
               for _ in range(3))
    out = flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                          causal=causal, block_q=16, block_k=16)
    np.testing.assert_allclose(np.asarray(out),
                               dense_attention(q, k, v, causal),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_grads_match_dense(rng, causal):
    B, T, H, D = 1, 32, 2, 8
    q, k, v = (rng.standard_normal((B, T, H, D)).astype(np.float32)
               for _ in range(3))
    tgt = rng.standard_normal((B, T, H, D)).astype(np.float32)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal=causal, block_q=8, block_k=8)
        return jnp.mean((o - tgt) ** 2)

    def loss_dense(q, k, v):
        d = q.shape[-1]
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * d ** -0.5
        if causal:
            mask = jnp.tril(jnp.ones((T, T), bool))
            s = jnp.where(mask[None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", p, v)
        return jnp.mean((o - tgt) ** 2)

    args = tuple(map(jnp.asarray, (q, k, v)))
    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(*args)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(*args)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-4)


def test_flash_non_divisible_seq_default_blocks(rng):
    # T=17 with the default (256, 512) blocks clamps to one ragged block.
    B, T, H, D = 1, 17, 2, 8
    q, k, v = (rng.standard_normal((B, T, H, D)).astype(np.float32)
               for _ in range(3))
    out = flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                          causal=False)
    np.testing.assert_allclose(np.asarray(out),
                               dense_attention(q, k, v, False),
                               rtol=1e-4, atol=1e-4)


def test_flash_cross_attention_shapes(rng):
    B, Tq, Tk, H, D = 1, 16, 32, 2, 8
    q = jnp.asarray(rng.standard_normal((B, Tq, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, Tk, H, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, Tk, H, D)), jnp.float32)
    out = flash_attention(q, k, v, causal=False, block_q=8, block_k=8)
    assert out.shape == (B, Tq, H, D)
    np.testing.assert_allclose(
        np.asarray(out),
        dense_attention(np.asarray(q), np.asarray(k), np.asarray(v), False),
        rtol=1e-4, atol=1e-4)


def test_unknown_attention_impl_raises(rng):
    from horovod_tpu.ops.attention import multihead_attention
    q = jnp.zeros((1, 8, 1, 4))
    with pytest.raises(ValueError, match="unknown attention impl"):
        multihead_attention(q, q, q, impl="Flash", causal=False)
    # ... including through a model config typo.
    from horovod_tpu.models.gpt2 import GPT2, GPT2Config
    cfg = GPT2Config.tiny(attention="pallas")
    tokens = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(ValueError, match="unknown attention impl"):
        GPT2(cfg).init(jax.random.PRNGKey(0), tokens)


def test_gpt2_ring_flash_matches_ring_dense(rng):
    # Sequence-parallel GPT-2: the ring-flash path must equal the jnp ring
    # path on an sp-sharded mesh.
    import horovod_tpu as hvd
    from jax.sharding import PartitionSpec as P
    from horovod_tpu.models.gpt2 import GPT2, GPT2Config
    from horovod_tpu.parallel import make_mesh

    tokens = jnp.asarray(rng.integers(0, 256, (2, 64)), jnp.int32)

    # init outside shard_map must not trace the ring ops — use the dense
    # single-device config (identical param structure).
    params = GPT2(GPT2Config.tiny(dtype=jnp.float32)).init(
        jax.random.PRNGKey(0), tokens[:, :8])

    def run(attention):
        cfg = GPT2Config.tiny(dtype=jnp.float32, use_ring_attention=True,
                              attention=attention)
        model = GPT2(cfg)
        hvd.init(axis_name="sp")
        try:
            fwd = hvd.spmd(lambda p, t: model.apply(p, t),
                           in_specs=(P(), P(None, "sp")),
                           out_specs=P(None, "sp"))
            return np.asarray(fwd(params, tokens))
        finally:
            hvd.init()  # restore the default communicator for other tests

    # Both ring variants must equal the single-device full-sequence model —
    # not merely each other (a shared defect, e.g. local-position embedding
    # under sp, would slip a pairwise check).
    ref_model = GPT2(GPT2Config.tiny(dtype=jnp.float32))
    want = np.asarray(ref_model.apply(params, tokens))
    got_flash, got_dense = run("flash"), run("dense")
    np.testing.assert_allclose(got_dense, want, rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(got_flash, want, rtol=2e-3, atol=2e-3)


def test_ring_path_rejects_unknown_impl():
    from horovod_tpu.models.gpt2 import GPT2, GPT2Config
    cfg = GPT2Config.tiny(attention="sparse", use_ring_attention=True)
    tokens = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(ValueError, match="ring path"):
        GPT2(cfg).init(jax.random.PRNGKey(0), tokens)


def test_flash_causal_requires_square():
    q = jnp.zeros((1, 16, 1, 8))
    k = jnp.zeros((1, 32, 1, 8))
    with pytest.raises(ValueError):
        flash_attention(q, k, v=k, causal=True)


@pytest.mark.parametrize("tq,tk", [(17, 17), (40, 24)])
def test_flash_ragged_blocks_match_dense(rng, tq, tk):
    # Lengths that don't divide the block size exercise the cdiv grid +
    # position-masked edge blocks (ViT's 197-token case).
    B, H, D = 1, 2, 8
    q = rng.standard_normal((B, tq, H, D)).astype(np.float32)
    k = rng.standard_normal((B, tk, H, D)).astype(np.float32)
    v = rng.standard_normal((B, tk, H, D)).astype(np.float32)
    out = flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                          causal=False, block_q=16, block_k=16)
    np.testing.assert_allclose(np.asarray(out),
                               dense_attention(q, k, v, False),
                               rtol=1e-4, atol=1e-4)


def test_flash_ragged_grads_match_dense(rng):
    B, T, H, D = 1, 20, 2, 8

    def run(attn):
        q, k, v = (jnp.asarray(rng2.standard_normal((B, T, H, D)),
                               jnp.float32) for rng2 in
                   (np.random.default_rng(i) for i in range(3)))

        def loss(q, k, v):
            return jnp.mean(attn(q, k, v) ** 2)
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    def dense(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * D ** -0.5
        mask = jnp.tril(jnp.ones((T, T), bool))
        s = jnp.where(mask[None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    gf = run(lambda q, k, v: flash_attention(q, k, v, causal=True,
                                             block_q=8, block_k=8))
    gd = run(dense)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-4)


def test_flash_key_bias_matches_masked_dense(rng):
    # key_bias carries a BERT-style key-padding mask through the kernel.
    B, T, H, D = 2, 32, 2, 8
    q, k, v = (rng.standard_normal((B, T, H, D)).astype(np.float32)
               for _ in range(3))
    valid = np.ones((B, T), bool)
    valid[0, 20:] = False
    valid[1, 5:] = False
    bias = np.where(valid, 0.0, -1e30).astype(np.float32)

    out = flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                          causal=False, key_bias=jnp.asarray(bias),
                          block_q=8, block_k=8)
    # Dense reference with the same additive bias.
    s = (np.einsum("bqhd,bkhd->bhqk", q, k) * D ** -0.5 +
         bias[:, None, None, :])
    s = s - s.max(axis=-1, keepdims=True)
    p = np.exp(s)
    p = p / p.sum(axis=-1, keepdims=True)
    ref = np.einsum("bhqk,bkhd->bqhd", p, v)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-4, atol=1e-4)


def test_flash_key_bias_gradient_matches_dense(rng):
    # key_bias is differentiable (ALiBi-style learned biases).
    B, T, H, D = 2, 24, 2, 8
    q, k, v = (jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.float32)
               for _ in range(3))
    bias0 = jnp.asarray(rng.standard_normal((B, T)), jnp.float32)

    def loss_flash(bias):
        o = flash_attention(q, k, v, causal=False, key_bias=bias,
                            block_q=8, block_k=8)
        return jnp.mean(o ** 2)

    def loss_dense(bias):
        s = (jnp.einsum("bqhd,bkhd->bhqk", q, k) * D ** -0.5 +
             bias[:, None, None, :])
        p = jax.nn.softmax(s, axis=-1)
        return jnp.mean(jnp.einsum("bhqk,bkhd->bqhd", p, v) ** 2)

    gf = jax.grad(loss_flash)(bias0)
    gd = jax.grad(loss_dense)(bias0)
    np.testing.assert_allclose(np.asarray(gf), np.asarray(gd),
                               rtol=1e-3, atol=1e-5)


def test_dense_and_flash_agree_on_fully_masked_rows(rng):
    # An all-padding batch item must yield zeros from both impls.
    from horovod_tpu.ops.attention import multihead_attention
    B, T, H, D = 2, 16, 2, 8
    q, k, v = (jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.float32)
               for _ in range(3))
    mask = jnp.asarray(np.array([[True] * T, [False] * T]))
    out_d = multihead_attention(q, k, v, impl="dense", causal=False,
                                key_mask=mask)
    out_f = multihead_attention(q, k, v, impl="flash", causal=False,
                                key_mask=mask)
    np.testing.assert_allclose(np.asarray(out_d[1]), 0.0, atol=1e-6)
    np.testing.assert_allclose(np.asarray(out_f[1]), 0.0, atol=1e-6)
    np.testing.assert_allclose(np.asarray(out_d[0]), np.asarray(out_f[0]),
                               rtol=1e-4, atol=1e-4)


def test_bert_flash_config_matches_dense(rng):
    from horovod_tpu.models.bert import Bert, BertConfig
    import dataclasses
    cfg_d = dataclasses.replace(BertConfig.tiny(), dtype=jnp.float32)
    cfg_f = dataclasses.replace(cfg_d, attention="flash")
    tokens = jnp.asarray(rng.integers(0, 256, (2, 24)), jnp.int32)
    types = jnp.zeros_like(tokens)
    mask = jnp.asarray(np.arange(24)[None, :] <
                       np.array([24, 13])[:, None])  # one padded row
    params = Bert(cfg_d).init(jax.random.PRNGKey(0), tokens, types, mask)
    out_d = Bert(cfg_d).apply(params, tokens, types, mask)
    out_f = Bert(cfg_f).apply(params, tokens, types, mask)
    for a, b in zip(jax.tree_util.tree_leaves(out_d),
                    jax.tree_util.tree_leaves(out_f)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-3)


def test_vit_flash_config_matches_dense(rng):
    from horovod_tpu.models.vit import ViT, ViTConfig
    import dataclasses
    cfg_d = dataclasses.replace(ViTConfig.tiny(), dtype=jnp.float32)
    cfg_f = dataclasses.replace(cfg_d, attention="flash")
    images = jnp.asarray(rng.standard_normal((2, 32, 32, 3)), jnp.float32)
    params = ViT(cfg_d).init(jax.random.PRNGKey(0), images)
    out_d = ViT(cfg_d).apply(params, images)
    out_f = ViT(cfg_f).apply(params, images)
    np.testing.assert_allclose(np.asarray(out_d), np.asarray(out_f),
                               rtol=2e-3, atol=2e-3)


def test_gpt2_flash_config_matches_dense(rng):
    from horovod_tpu.models.gpt2 import GPT2, GPT2Config
    cfg_d = GPT2Config.tiny(dtype=jnp.float32)
    cfg_f = GPT2Config.tiny(dtype=jnp.float32, attention="flash")
    tokens = jnp.asarray(rng.integers(0, 256, (2, 32)), jnp.int32)
    params = GPT2(cfg_d).init(jax.random.PRNGKey(0), tokens)
    out_d = GPT2(cfg_d).apply(params, tokens)
    out_f = GPT2(cfg_f).apply(params, tokens)
    np.testing.assert_allclose(np.asarray(out_d), np.asarray(out_f),
                               rtol=1e-3, atol=1e-3)


def test_gpt2_striped_sp_matches_single_device(rng):
    """Striped sequence-parallel GPT-2: logits equal the single-device
    model on un-striped order, and striped_lm_loss equals the full-sequence
    loss exactly (it covers every token pair — the contiguous shift drops
    shard boundaries)."""
    import horovod_tpu as hvd
    from jax.sharding import PartitionSpec as P
    from horovod_tpu.models.gpt2 import (GPT2, GPT2Config, loss_fn,
                                         striped_lm_loss)

    N = 8
    tokens = jnp.asarray(rng.integers(0, 256, (2, 64)), jnp.int32)
    params = GPT2(GPT2Config.tiny(dtype=jnp.float32)).init(
        jax.random.PRNGKey(0), tokens[:, :8])

    from conftest import stripe_seq, unstripe_seq

    def stripe(x):
        return jnp.asarray(stripe_seq(x, N))

    def unstripe(y):
        return unstripe_seq(y, N)

    for attention in ("dense", "flash"):
        cfg = GPT2Config.tiny(dtype=jnp.float32, use_ring_attention=True,
                              ring_layout="striped", attention=attention)
        model = GPT2(cfg)
        hvd.init(axis_name="sp")
        try:
            def body(p, t):
                logits = model.apply(p, t)
                return logits, striped_lm_loss(logits, t)[None]

            fwd = hvd.spmd(body, in_specs=(P(), P(None, "sp")),
                           out_specs=(P(None, "sp"), P("sp")))
            logits_s, losses = fwd(params, stripe(tokens))
        finally:
            hvd.init()

        ref_model = GPT2(GPT2Config.tiny(dtype=jnp.float32))
        ref_logits = ref_model.apply(params, tokens)
        np.testing.assert_allclose(unstripe(logits_s),
                                   np.asarray(ref_logits),
                                   rtol=2e-3, atol=2e-3)
        ref_loss = loss_fn(ref_logits, tokens)
        # every shard returns the same replicated global loss
        np.testing.assert_allclose(np.asarray(losses),
                                   float(ref_loss), rtol=1e-4)


def test_gpt2_ulysses_matches_single_device(rng):
    """sp_impl='ulysses': all-to-all sequence parallelism in the model zoo —
    dense and flash local attention both equal the single-device model."""
    import horovod_tpu as hvd
    from jax.sharding import PartitionSpec as P
    from horovod_tpu.models.gpt2 import GPT2, GPT2Config

    tokens = jnp.asarray(rng.integers(0, 256, (2, 64)), jnp.int32)
    params = GPT2(GPT2Config.tiny(dtype=jnp.float32)).init(
        jax.random.PRNGKey(0), tokens[:, :8])

    def run(attention):
        cfg = GPT2Config.tiny(dtype=jnp.float32, use_ring_attention=True,
                              sp_impl="ulysses", attention=attention)
        model = GPT2(cfg)
        hvd.init(axis_name="sp")
        try:
            fwd = hvd.spmd(lambda p, t: model.apply(p, t),
                           in_specs=(P(), P(None, "sp")),
                           out_specs=P(None, "sp"))
            return np.asarray(fwd(params, tokens))
        finally:
            hvd.init()

    want = np.asarray(GPT2(GPT2Config.tiny(dtype=jnp.float32))
                      .apply(params, tokens))
    np.testing.assert_allclose(run("dense"), want, rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(run("flash"), want, rtol=2e-3, atol=2e-3)


def test_gpt2_ulysses_rejects_striped_layout():
    from horovod_tpu.models.gpt2 import GPT2, GPT2Config
    cfg = GPT2Config.tiny(use_ring_attention=True, sp_impl="ulysses",
                          ring_layout="striped")
    with pytest.raises(ValueError, match="contiguous"):
        GPT2(cfg).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))


def test_gpt2_unknown_sp_impl_rejected():
    from horovod_tpu.models.gpt2 import GPT2, GPT2Config
    cfg = GPT2Config.tiny(use_ring_attention=True, sp_impl="ringish")
    with pytest.raises(ValueError, match="sp_impl"):
        GPT2(cfg).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))


def test_gpt2_unknown_ring_layout_rejected():
    from horovod_tpu.models.gpt2 import GPT2, GPT2Config
    cfg = GPT2Config.tiny(use_ring_attention=True, ring_layout="stripe")
    with pytest.raises(ValueError, match="ring_layout"):
        GPT2(cfg).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))


class TestFlashSegments:
    """Sequence-packing segment masks inside the pallas kernels: the
    score-tile mask (same-segment pairs only) in forward and both
    backward kernels == the dense reference with the same blocking."""

    def _dense_ref(self, q, k, v, seg, causal):
        from horovod_tpu.ops.attention import multihead_attention
        return multihead_attention(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), impl="dense",
            causal=causal, segment_ids=jnp.asarray(seg),
            out_dtype=jnp.float32)

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("T", [64, 50])   # 50: ragged edge tiles
    def test_packed_flash_matches_dense(self, rng, causal, T):
        B, H, D = 2, 2, 16
        q, k, v = (rng.standard_normal((B, T, H, D)).astype(np.float32)
                   for _ in range(3))
        seg = np.cumsum(rng.random((B, T)) < 0.1, axis=1).astype(np.int32)
        out = flash_attention(jnp.asarray(q), jnp.asarray(k),
                              jnp.asarray(v), causal=causal,
                              segment_ids=jnp.asarray(seg),
                              block_q=16, block_k=16)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(self._dense_ref(q, k, v, seg,
                                                        causal)),
            rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("causal", [False, True])
    def test_packed_flash_grads_match_dense(self, rng, causal):
        B, T, H, D = 2, 64, 2, 16
        q, k, v = (rng.standard_normal((B, T, H, D)).astype(np.float32)
                   for _ in range(3))
        seg = jnp.asarray(
            np.cumsum(rng.random((B, T)) < 0.1, axis=1).astype(np.int32))
        do = rng.standard_normal((B, T, H, D)).astype(np.float32)

        def loss_flash(q, k, v):
            o = flash_attention(q, k, v, causal=causal, segment_ids=seg,
                                block_q=16, block_k=16)
            return jnp.sum(o.astype(jnp.float32) * do)

        def loss_dense(q, k, v):
            from horovod_tpu.ops.attention import multihead_attention
            o = multihead_attention(q, k, v, impl="dense", causal=causal,
                                    segment_ids=seg,
                                    out_dtype=jnp.float32)
            return jnp.sum(o * do)

        args = (jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(*args)
        gd = jax.grad(loss_dense, argnums=(0, 1, 2))(*args)
        for a, b in zip(gf, gd):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4)

    def test_packed_flash_with_key_bias(self, rng):
        """Segments compose with the per-key bias (padding inside a
        packed batch)."""
        B, T, H, D = 2, 64, 2, 16
        q, k, v = (rng.standard_normal((B, T, H, D)).astype(np.float32)
                   for _ in range(3))
        seg = np.cumsum(rng.random((B, T)) < 0.1, axis=1).astype(np.int32)
        mask = np.arange(T)[None, :] < np.array([[T - 7], [T - 2]])
        bias = np.where(mask, 0.0, -1e30).astype(np.float32)
        out = flash_attention(jnp.asarray(q), jnp.asarray(k),
                              jnp.asarray(v), causal=False,
                              key_bias=jnp.asarray(bias),
                              segment_ids=jnp.asarray(seg),
                              block_q=16, block_k=16)
        from horovod_tpu.ops.attention import multihead_attention
        want = multihead_attention(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), impl="dense",
            causal=False, key_mask=jnp.asarray(mask),
            segment_ids=jnp.asarray(seg), out_dtype=jnp.float32)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)


# --- the causal compute chunk (tile-table entries with a ``chunk``) -------

def _fa():
    import importlib
    return importlib.import_module("horovod_tpu.ops.flash_attention")


def _kernel_calls(jaxpr_text):
    return {name: jaxpr_text.count(f"name={name}")
            for name in ("flash_fwd", "flash_dq", "flash_dkv")}


def _masked_dense_loss(q, k, v, tgt, offset=0, seg=None, key_bias=None):
    """softmax attention under the causal mask (shifted by ``offset``),
    segment ids and a key bias, in plain jnp; rows with no visible key
    give zeros, as the kernel does."""
    t, d = q.shape[1], q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * d ** -0.5
    if key_bias is not None:
        s = s + key_bias[:, None, None, :]
    ok = (jnp.arange(t)[:, None] + offset >= jnp.arange(t)[None, :])[None,
                                                                     None]
    if seg is not None:
        ok = ok & (seg[:, None, :, None] == seg[:, None, None, :])
    p = jax.nn.softmax(jnp.where(ok, s, -1e30), axis=-1)
    p = jnp.where(jnp.any(ok, axis=-1, keepdims=True), p, 0.0)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v)
    return jnp.mean((o - tgt) ** 2)


# (T, block_q, block_k, chunk, causal_offset, segment ids, key bias)
_CHUNKED = {
    "causal": (64, 16, 64, 16, 0, False, False),
    "offset-1": (64, 16, 64, 16, -1, False, False),
    "segment_ids": (64, 16, 64, 16, 0, True, False),
    "key_bias": (64, 16, 64, 16, 0, False, True),
    "ragged": (72, 16, 128, 16, 0, False, False),       # 72 = 4.5 chunks
    "ragged_q_too": (100, 32, 256, 16, 0, True, True),
    "chunk_over_q": (64, 16, 64, 32, 0, False, False),  # chunk > block_q
    # the K tile does not hold every key: the grid steps over K as before
    "k_tile_short": (128, 32, 64, 16, 0, False, False),
}


@pytest.mark.parametrize("case", list(_CHUNKED), ids=list(_CHUNKED))
def test_chunked_causal_matches_dense(rng, case):
    """chunk < block_k: the loop inside the tile, whose length is the
    diagonal, against dense attention: forward and all three gradients
    (and the bias's), with every mask that shares ``_mask_scores``."""
    fa = _fa()
    T, bq, bk, chunk, offset, packed, biased = _CHUNKED[case]
    B, H, D = 2, 2, 8
    q, k, v, tgt = (jnp.asarray(rng.standard_normal((B, T, H, D)),
                                jnp.float32) for _ in range(4))
    seg = (jnp.asarray(np.sort(rng.integers(0, 3, (B, T)), axis=1),
                       jnp.int32) if packed else None)
    bias = (jnp.asarray(rng.standard_normal((B, T)), jnp.float32)
            if biased else None)
    tiles = (bq, bk, bq, bk, chunk, chunk)

    def loss_flash(q, k, v, bias):
        o = fa._attend(q, k, v, True, D ** -0.5, bias, seg, tiles, offset)
        return jnp.mean((o - tgt) ** 2)

    def loss_dense(q, k, v, bias):
        return _masked_dense_loss(q, k, v, tgt, offset, seg, bias)

    argnums = (0, 1, 2, 3) if biased else (0, 1, 2)
    # the loop is there: two in each kernel, and two kernels: the forward
    # and the dK/dV kernel that also yields dQ or, where it keeps its tile
    # whole to sum the bias's gradient along lanes, the dQ kernel
    text = str(jax.make_jaxpr(jax.grad(loss_flash, argnums))(q, k, v, bias))
    assert text.count("while[") == {"k_tile_short": 0}.get(case, 4)
    assert _kernel_calls(text)["flash_dq"] == (
        1 if biased or case == "k_tile_short" else 0)
    lf, gf = jax.value_and_grad(loss_flash, argnums)(q, k, v, bias)
    ld, gd = jax.value_and_grad(loss_dense, argnums)(q, k, v, bias)
    np.testing.assert_allclose(float(lf), float(ld), rtol=1e-5)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-5)


def test_chunked_and_unchunked_kernels_agree(rng):
    """The same inputs through the loop of chunks and through the one
    whole tile: the sums are taken in another order, nothing else."""
    fa = _fa()
    B, T, H, D = 1, 128, 2, 16
    q, k, v = (jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.float32)
               for _ in range(3))

    def grads(chunk):
        tiles = (32, 128, 32, 128, chunk, chunk)
        return jax.value_and_grad(
            lambda q, k, v: jnp.sum(fa._attend(
                q, k, v, True, D ** -0.5, None, None, tiles) ** 2),
            argnums=(0, 1, 2))(q, k, v)

    (whole, g_whole), (looped, g_looped) = grads(None), grads(32)
    np.testing.assert_allclose(float(looped), float(whole), rtol=1e-6)
    for a, b in zip(g_looped, g_whole):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("t,block_q,block_k,chunk,offset", [
    (1024, 256, 1024, 256, 0),      # 10 of 16
    (1024, 256, 1024, 512, 0),      # 6 of 8
    (1024, 512, 1024, 512, 0),      # 3 of 4
    (1024, 128, 1024, 128, 0),
    (200, 32, 256, 16, 0),          # ragged: T is no multiple of anything
    (1024, 256, 512, None, 0),      # no chunk: the grid's tiles
    (1024, 128, 512, 128, 0),       # a K tile short of the keys: the same
    (256, 64, 256, 32, -1),         # strict causal (striped ring layouts)
])
def test_causal_tiles_counts_what_the_dense_mask_shows(t, block_q, block_k,
                                                       chunk, offset):
    fa = _fa()
    c = chunk if chunk and block_k >= t else min(block_k, t)
    mask = np.tril(np.ones((t, t), bool), k=offset)
    nq, nc = -(-t // block_q), -(-t // c)
    padded = np.zeros((nq * block_q, nc * c), bool)
    padded[:t, :t] = mask
    seen = padded.reshape(nq, block_q, nc, c).any(axis=(1, 3))
    assert fa.causal_tiles(t, block_q, block_k, chunk, offset) == (
        int(seen.sum()), nq * nc)
    if (t, block_q, chunk) == (1024, 256, 256):
        assert int(seen.sum()) == 10 and nq * nc == 16


# --- the one-kernel backward (a resident K tile with a chunk loop) --------

# (T, block_q, chunk, causal_offset, segment ids, key bias, dtype, head_dim)
_FUSED = {
    # the table's own entry for GPT-2 medium's attention (head 64, T 1024)
    "table_entry_1024": (1024, 512, 512, 0, False, False, "bfloat16", 64),
    "ragged": (200, 64, 64, 0, False, False, "float32", 16),
    "offset-1": (256, 64, 64, -1, False, False, "float32", 16),
    "segment_ids": (256, 64, 64, 0, True, False, "float32", 16),
    "bias_gradient_discarded": (200, 32, 64, 0, False, True, "float32", 16),
    "bf16": (256, 64, 64, 0, False, False, "bfloat16", 64),
}


@pytest.mark.parametrize("case", list(_FUSED), ids=list(_FUSED))
def test_one_kernel_backward_equals_the_two_kernel_one(rng, case):
    """Where the dK/dV kernel loops over the chunks of a resident K tile it
    also sums dQ, and no ``flash_dq`` call is made. The two-kernel
    backward of the same tiles is still there where a bias gradient is
    tracked (``flash_dq`` with the same loop of chunks, ``flash_dkv`` with
    its tile whole): dQ is bit for bit that kernel's, dK and dV are its
    sums taken in another order."""
    fa = _fa()
    T, bq, chunk, offset, packed, biased, dtype, D = _FUSED[case]
    if case == "table_entry_1024":
        from horovod_tpu.ops import tile_table
        assert tile_table.lookup_full(D, T, dtype, "causal")[2:] == (
            bq, T, 512, chunk)
    B, H = 1, 2
    bk = -(-T // chunk) * chunk
    q, k, v, do = (jnp.asarray(rng.standard_normal((B * H, T, D)), dtype)
                   for _ in range(4))
    seg = (jnp.asarray(np.sort(rng.integers(0, 3, (B, T)), axis=1),
                       jnp.int32).reshape(B, T, 1) if packed else None)
    bias = (jnp.asarray(rng.standard_normal((B, T, 1)), jnp.float32)
            if biased else None)
    scale = D ** -0.5

    def backward(bias, want_db):
        o, lse = fa._fwd(q, k, v, bias, seg, seg, H, scale, True, bq, bk,
                         offset=offset, chunk=chunk)

        def bwd(q, k, v, o, lse, do):
            return fa._bwd(H, scale, True, bq, bk,
                           (q, k, v, bias, seg, seg, o, lse), do,
                           offset=offset, want_db=want_db, chunk=chunk)[:3]
        calls = _kernel_calls(str(jax.make_jaxpr(bwd)(q, k, v, o, lse, do)))
        return calls, bwd(q, k, v, o, lse, do)

    calls, one = backward(bias, want_db=False)
    assert calls == {"flash_fwd": 0, "flash_dq": 0, "flash_dkv": 1}
    # a zero bias adds 0.0 to every score: the same numbers, two kernels
    zero = jnp.zeros((B, T, 1), jnp.float32)
    calls, two = backward(zero if bias is None else bias, want_db=True)
    assert calls == {"flash_fwd": 0, "flash_dq": 1, "flash_dkv": 1}
    as32 = lambda x: np.asarray(x.astype(jnp.float32))
    assert one[0].dtype == two[0].dtype == q.dtype
    np.testing.assert_array_equal(as32(one[0]), as32(two[0]))
    tol = dict(rtol=2e-2, atol=2e-2) if dtype == "bfloat16" \
        else dict(rtol=1e-5, atol=1e-5)
    for a, b in zip(one[1:], two[1:]):
        np.testing.assert_allclose(as32(a), as32(b), **tol)


def _two_kernel_paths():
    """name -> (function of (q, k, v[, bias]), shapes): calls whose
    backward stays ``flash_dq`` + ``flash_dkv`` (their jaxprs were diffed
    equal to the parent's when the one-kernel backward came, PR 36)."""
    fa = _fa()
    from horovod_tpu.ops import tile_table
    assert tile_table.lookup_full(64, 1024, "bfloat16", "causal")[4:] == (
        512, 512)
    return {
        "block_diffusion": (lambda q, k, v: fa.flash_attention(
            q, k, v, block_diffusion=(128, 4)), (1, 256, 2, 64), None),
        # the table's entry (K resident, chunks of 512), but the bias's
        # gradient is summed along lanes and keeps the dK/dV tile whole
        "tracked_bias_gradient": (lambda q, k, v, b: fa.flash_attention(
            q, k, v, causal=True, key_bias=b), (1, 1024, 2, 64), (1, 1024)),
        "explicit_tiles": (lambda q, k, v: fa.flash_attention(
            q, k, v, causal=True, block_q=128, block_k=1024,
            block_q_bwd=128, block_k_bwd=1024), (1, 1024, 2, 64), None),
        "no_chunk_in_the_entry": (lambda q, k, v: fa.flash_attention(
            q, k, v, causal=True), (1, 4096, 2, 64), None),
    }


@pytest.mark.parametrize("path", ["block_diffusion", "tracked_bias_gradient",
                                  "explicit_tiles", "no_chunk_in_the_entry"])
def test_backward_stays_two_kernels_off_the_resident_path(monkeypatch, path):
    fa = _fa()
    # lowered as for the chip: the jaxpr holds the calls, not their
    # interpretation, and any compiler_params they carry
    monkeypatch.setattr(fa, "_use_interpret", lambda: False)
    fn, shape, bias_shape = _two_kernel_paths()[path]
    args = [jnp.zeros(shape, jnp.bfloat16)] * 3
    if bias_shape:
        args.append(jnp.zeros(bias_shape, jnp.float32))
    text = str(jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(fn(*a).astype(jnp.float32)),
        argnums=tuple(range(len(args)))))(*args))
    assert _kernel_calls(text) == {"flash_fwd": 1, "flash_dq": 1,
                                   "flash_dkv": 1}
    # nothing here is over the compiler's default: no call asks for VMEM
    assert "vmem_limit_bytes" not in text


def test_ring_flash_backward_stays_two_kernels(monkeypatch):
    """``ops/ring_flash.py`` hands ``_bwd`` explicit tiles, a precomputed
    ``delta`` and an offset, and no chunk: ``flash_dq`` + ``flash_dkv`` a
    hop, under the causal, the full and the strict mask."""
    from jax.sharding import Mesh, PartitionSpec as P
    from horovod_tpu.ops.ring_flash import ring_flash_attention
    fa = _fa()
    monkeypatch.setattr(fa, "_use_interpret", lambda: False)
    mesh = Mesh(np.array(jax.devices()[:4]), ("sp",))
    x = jnp.zeros((1, 4096, 2, 64), jnp.bfloat16)
    for layout in ("contiguous", "striped"):
        ring = jax.shard_map(
            lambda q, k, v: ring_flash_attention(q, k, v, "sp", causal=True,
                                                 layout=layout),
            mesh=mesh, in_specs=(P(None, "sp"),) * 3,
            out_specs=P(None, "sp"), check_vma=False)
        text = str(jax.make_jaxpr(jax.grad(
            lambda q, k, v: jnp.sum(ring(q, k, v).astype(jnp.float32)),
            argnums=(0, 1, 2)))(x, x, x))
        calls = _kernel_calls(text)
        assert calls["flash_dq"] == calls["flash_dkv"] > 0
        assert "vmem_limit_bytes" not in text


# --- the sliding window (causal with a lower edge) -------------------------

def _window_dense_loss(q, k, v, tgt, window, offset=0, seg=None):
    """softmax attention over ``0 <= t + offset - j < window`` (and segment
    ids), in plain jnp; rows with no visible key give zeros."""
    t, d = q.shape[1], q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * d ** -0.5
    back = jnp.arange(t)[:, None] + offset - jnp.arange(t)[None, :]
    ok = ((back >= 0) & (back < window))[None, None]
    if seg is not None:
        ok = ok & (seg[:, None, :, None] == seg[:, None, None, :])
    p = jax.nn.softmax(jnp.where(ok, s, -1e30), axis=-1)
    p = jnp.where(jnp.any(ok, axis=-1, keepdims=True), p, 0.0)
    return jnp.mean((jnp.einsum("bhqk,bkhd->bqhd", p, v) - tgt) ** 2)


# (T, window, block_q, block_k, chunk, causal_offset, segment ids)
_WINDOWED = {
    # the plain grid: tiles under the band and over the diagonal skipped
    "grid": (128, 40, 32, 32, None, 0, False),
    "grid_window_under_a_tile": (128, 8, 32, 32, None, 0, False),
    "grid_ragged": (100, 24, 32, 16, None, 0, False),
    "grid_unequal_tiles": (200, 64, 16, 64, None, 0, True),
    "grid_offset-1": (128, 40, 32, 32, None, -1, False),
    # the loop over chunks of a resident K tile: from the band to the
    # diagonal, the chunks neither edge crosses unmasked between
    "chunked": (256, 100, 32, 256, 32, 0, False),
    "chunked_window_under_a_chunk": (128, 8, 32, 128, 32, 0, False),
    "chunked_one_key": (96, 1, 32, 128, 32, 0, False),
    "chunked_ragged_packed": (200, 64, 64, 256, 16, 0, True),
    "chunked_chunk_over_q": (256, 100, 32, 256, 64, 0, False),
    "chunked_offset-1": (256, 100, 64, 256, 32, -1, False),
}


@pytest.mark.parametrize("case", list(_WINDOWED), ids=list(_WINDOWED))
def test_window_kernels_match_the_dense_mask(rng, case):
    """``window=W``: forward and all three gradients against dense attention
    under the band, at T not a multiple of the tiles, W smaller and larger
    than a tile, with segment ids, on the plain grid and under a chunk (the
    one-kernel backward: no ``flash_dq``)."""
    fa = _fa()
    T, W, bq, bk, chunk, offset, packed = _WINDOWED[case]
    B, H, D = 2, 2, 8
    q, k, v, tgt = (jnp.asarray(rng.standard_normal((B, T, H, D)),
                                jnp.float32) for _ in range(4))
    seg = (jnp.asarray(np.sort(rng.integers(0, 3, (B, T)), axis=1),
                       jnp.int32) if packed else None)
    tiles = (bq, bk, bq, bk, chunk, chunk)

    def loss_flash(q, k, v):
        o = fa._attend(q, k, v, True, D ** -0.5, None, seg, tiles, offset,
                       None, W)
        return jnp.mean((o - tgt) ** 2)

    text = str(jax.make_jaxpr(jax.grad(loss_flash, (0, 1, 2)))(q, k, v))
    # three runs of chunks in each of the two kernels, or none
    assert text.count("while[") == (6 if chunk else 0)
    assert _kernel_calls(text)["flash_dq"] == (0 if chunk else 1)
    lf, gf = jax.value_and_grad(loss_flash, (0, 1, 2))(q, k, v)
    ld, gd = jax.value_and_grad(
        lambda q, k, v: _window_dense_loss(q, k, v, tgt, W, offset, seg),
        (0, 1, 2))(q, k, v)
    np.testing.assert_allclose(float(lf), float(ld), rtol=1e-5)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-5)


@pytest.mark.parametrize("impl", ["dense", "flash"])
def test_multihead_attention_takes_a_window(rng, impl):
    """Both impls of the zoo's dispatch, and a key-padding mask beside
    the window (the flash kernels' key bias composes with it)."""
    from horovod_tpu.ops.attention import multihead_attention
    B, T, H, D, W = 2, 48, 2, 8, 12
    q, k, v, tgt = (jnp.asarray(rng.standard_normal((B, T, H, D)),
                                jnp.float32) for _ in range(4))
    got = multihead_attention(q, k, v, impl=impl, causal=True, window=W,
                              flash_blocks=(16, 16))
    want = jax.grad(lambda v: _window_dense_loss(q, k, v, tgt, W))(v)
    got_grad = jax.grad(lambda v: jnp.mean((multihead_attention(
        q, k, v, impl=impl, causal=True, window=W,
        flash_blocks=(16, 16)) - tgt) ** 2))(v)
    np.testing.assert_allclose(np.asarray(got_grad), np.asarray(want),
                               rtol=1e-3, atol=1e-6)
    mask = jnp.asarray(rng.random((B, T)) > 0.3)
    masked = multihead_attention(q, k, v, impl=impl, causal=True, window=W,
                                 key_mask=mask, flash_blocks=(16, 16))
    other = multihead_attention(q, k, v, impl="dense" if impl == "flash"
                                else "flash", causal=True, window=W,
                                key_mask=mask, flash_blocks=(16, 16))
    np.testing.assert_allclose(np.asarray(masked), np.asarray(other),
                               rtol=1e-4, atol=1e-5)
    assert np.abs(np.asarray(masked) - np.asarray(got)).max() > 1e-3


@pytest.mark.parametrize("tiles", [(16, 16, 16, 16, None, None),
                                   (16, 64, 16, 64, 16, 16)],
                         ids=["grid", "chunked"])
def test_a_window_that_holds_every_key_is_the_causal_mask(rng, tiles):
    """W >= T: the public call runs the causal kernels themselves (the
    same jaxpr, the causal gauges), and the window kernels told to mask an
    edge no row reaches give the causal result."""
    import horovod_tpu as hvd
    from horovod_tpu import tracing
    fa = _fa()
    B, T, H, D = 1, 64, 2, 8
    q, k, v = (jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.float32)
               for _ in range(3))
    call = lambda **kw: (lambda q, k, v: fa.flash_attention(
        q, k, v, causal=True, block_q=16, block_k=16, **kw))
    causal = str(jax.make_jaxpr(call())(q, k, v))
    assert str(jax.make_jaxpr(call(window=T))(q, k, v)) == causal
    assert str(jax.make_jaxpr(call(window=T - 1))(q, k, v)) != causal
    with tracing.program("window_all"):
        jax.make_jaxpr(call(window=5 * T))(q, k, v)
    gauges = hvd.metrics.snapshot()["gauges"]
    said = lambda name: [s["value"] for s in gauges.get(name, ())
                         if s["labels"].get("program") == "window_all"]
    assert said("causal_tiles_visited") and not said("window_tiles_visited")
    attend = lambda window: jax.value_and_grad(lambda q, k, v: jnp.sum(
        fa._attend(q, k, v, True, D ** -0.5, None, None, tiles, 0, None,
                   window) ** 2), (0, 1, 2))(q, k, v)
    for a, b in zip(jax.tree_util.tree_leaves(attend(T)),
                    jax.tree_util.tree_leaves(attend(None))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_no_window_traces_as_it_did(rng):
    """``window=None`` adds nothing: the default call's jaxpr, forward and
    gradient, on the plain grid and under a chunk, is that of the call that
    never heard of a window, and holds neither the band's comparison nor
    its third run of chunks (the parent's jaxprs, compared once against a
    checkout of it: CHANGES.md)."""
    fa = _fa()
    q = jnp.zeros((1, 128, 2, 8), jnp.float32)
    grad = lambda fn: str(jax.make_jaxpr(jax.grad(
        lambda q, k, v: jnp.sum(fn(q, k, v)), (0, 1, 2)))(q, q, q))
    for tiles, loops in (((32, 32, 32, 32, None, None), 0),
                         ((32, 128, 32, 128, 32, 32), 4)):
        plain = grad(lambda q, k, v: fa._attend(
            q, k, v, True, 8 ** -0.5, None, None, tiles))
        assert plain == grad(lambda q, k, v: fa._attend(
            q, k, v, True, 8 ** -0.5, None, None, tiles, 0, None, None))
        assert plain.count("while[") == loops
        windowed = grad(lambda q, k, v: fa._attend(
            q, k, v, True, 8 ** -0.5, None, None, tiles, 0, None, 40))
        assert windowed != plain
        assert windowed.count("while[") == loops * 3 // 2


@pytest.mark.parametrize("t,window,block_q,block_k,chunk,offset", [
    (16384, 4096, 512, 16384, 512, 0),      # the benchmark's: 252 of 1024
    (1024, 256, 256, 1024, 256, 0),
    (1024, 100, 128, 1024, 64, 0),          # W under a chunk and a tile
    (1024, 300, 256, 1024, 512, 0),         # chunk over the Q tile
    (200, 64, 32, 256, 16, 0),              # ragged
    (1024, 256, 256, 256, None, 0),         # no chunk: the grid's tiles
    (1000, 130, 128, 64, None, 0),
    (256, 100, 64, 256, 32, -1),            # strict causal
    (1024, 1, 128, 1024, 128, 0),           # a row sees itself alone
])
def test_window_tiles_counts_what_the_dense_mask_shows(t, window, block_q,
                                                       block_k, chunk,
                                                       offset):
    fa = _fa()
    c = chunk if chunk and block_k >= t else min(block_k, t)
    back = np.arange(t)[:, None] + offset - np.arange(t)[None, :]
    mask = (back >= 0) & (back < window)
    nq, nc = -(-t // block_q), -(-t // c)
    padded = np.zeros((nq * block_q, nc * c), bool)
    padded[:t, :t] = mask
    seen = padded.reshape(nq, block_q, nc, c).any(axis=(1, 3))
    assert fa.window_tiles(t, window, block_q, block_k, chunk, offset) == (
        int(seen.sum()), nq * nc)
    if t == 16384:
        assert (int(seen.sum()), nq * nc) == (252, 1024)
        assert fa.causal_tiles(t, block_q, block_k, chunk) == (528, 1024)
        assert 100 * mask.mean() == pytest.approx(21.9, abs=0.05)


def test_the_chunks_between_the_edges_take_no_mask():
    """``_window_chunks``: against each Q tile, the chunks before
    ``inside`` are crossed by the band's lower edge, those from ``clear`` by
    the diagonal, and those between lie whole inside the band."""
    fa = _fa()
    for t, w, bq, c in ((1024, 256, 128, 64), (1024, 100, 128, 64),
                        (512, 300, 64, 128), (200, 64, 32, 16)):
        back = np.arange(t)[:, None] - np.arange(t)[None, :]
        mask = (back >= 0) & (back < w)
        nq, nc = -(-t // bq), -(-t // c)
        padded = np.zeros((nq * bq, nc * c), bool)
        padded[:t, :t] = mask
        tiles = padded.reshape(nq, bq, nc, c)
        rows = (np.arange(nq * bq) < t).reshape(nq, bq)
        cols = (np.arange(nc * c) < t).reshape(nc, c)
        first, inside, clear, visible = fa._window_chunks(
            np.arange(nq), bq, c, 0, t, w, xp=np)
        for i in range(nq):
            real = rows[i][:, None] & cols[:, None, :]      # (nc, bq, c)
            whole = (tiles[i].transpose(1, 0, 2) | ~real).all(axis=(1, 2))
            some = tiles[i].any(axis=(0, 2))
            assert some[first[i]:visible[i]].all()
            assert not some[:first[i]].any() and not some[visible[i]:].any()
            assert whole[inside[i]:clear[i]].all(), (t, w, bq, c, i)
            assert first[i] <= inside[i] <= clear[i] <= visible[i]


def test_a_window_is_refused_where_it_is_not_built(rng):
    from jax.sharding import Mesh, PartitionSpec as P
    from horovod_tpu.ops.attention import multihead_attention
    from horovod_tpu.ops.ring_flash import ring_flash_attention
    x = jnp.zeros((1, 16, 2, 8), jnp.float32)
    with pytest.raises(ValueError, match="needs causal=True"):
        flash_attention(x, x, x, causal=False, window=4)
    with pytest.raises(ValueError, match="at least one key"):
        flash_attention(x, x, x, causal=True, window=0)
    with pytest.raises(ValueError, match="block_diffusion"):
        flash_attention(x, x, x, block_diffusion=(8, 4), window=4)
    with pytest.raises(ValueError, match="needs causal=True"):
        multihead_attention(x, x, x, impl="dense", causal=False, window=4)
    mesh = Mesh(np.array(jax.devices()[:2]), ("sp",))
    ring = jax.shard_map(
        lambda q, k, v: ring_flash_attention(q, k, v, "sp", causal=True,
                                             window=4),
        mesh=mesh, in_specs=(P(None, "sp"),) * 3, out_specs=P(None, "sp"),
        check_vma=False)
    with pytest.raises(ValueError, match="no sliding window"):
        jax.make_jaxpr(ring)(x, x, x)


# --- the block-diffusion mask under the chunk loop (PR 38) -----------------

def _bd_visible(seq, blk):
    """The block-diffusion mask of ``2 * seq`` positions ``[noisy ; clean]``
    as the benchmark's plain reference writes it, from its words
    (``ops.attention.block_diffusion_mask`` is held to the same table in
    ``tests/test_sdar.py``)."""
    import os
    import sys
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    from reference import sdar_moe_ref
    return np.asarray(sdar_moe_ref.visible(seq, blk))


def _bd_dense_loss(q, k, v, tgt, seq, blk, seg=None, key_bias=None):
    d = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * d ** -0.5
    if key_bias is not None:
        s = s + key_bias[:, None, None, :]
    ok = jnp.asarray(_bd_visible(seq, blk))[None, None]
    if seg is not None:
        ok = ok & (seg[:, None, :, None] == seg[:, None, None, :])
    p = jax.nn.softmax(jnp.where(ok, s, -1e30), axis=-1)
    p = jnp.where(jnp.any(ok, axis=-1, keepdims=True), p, 0.0)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v)
    return jnp.mean((o - tgt) ** 2)


# (seq, block_len, block_q, chunk, segment ids, key bias): a row is 2 * seq
_BD_CHUNKED = {
    "q_is_chunk": (64, 4, 16, 16, False, False),
    "q_over_chunk": (64, 4, 32, 16, False, False),
    "q_under_chunk": (64, 4, 16, 32, False, False),
    "block_of_3": (48, 3, 16, 16, False, False),     # no power of two
    "block_of_6_q_over": (96, 6, 32, 16, False, False),
    "block_is_chunk": (64, 16, 16, 16, False, False),
    "block_over_chunk": (64, 32, 16, 16, False, False),
    "one_q_tile_a_half": (64, 8, 64, 32, False, False),
    "segment_ids": (64, 4, 16, 16, True, False),
    "key_bias": (64, 4, 16, 32, False, True),
    # the shapes the loop cannot take: the plain grid, two kernels
    "seq_no_whole_chunks": (48, 4, 16, 32, False, False),
    "q_tile_over_the_seam": (48, 4, 32, 16, False, False),
}
_BD_PLAIN = ("seq_no_whole_chunks", "q_tile_over_the_seam")


@pytest.mark.parametrize("case", list(_BD_CHUNKED), ids=list(_BD_CHUNKED))
def test_chunked_block_diffusion_matches_dense(rng, case):
    """The K axis resident and the loop over what the mask shows to a Q
    tile (``_bd_chunks``), against dense attention under
    ``block_diffusion_mask``: forward, dQ, dK, dV (and the bias's). Three
    runs of chunks in each of two kernels; where the loop cannot take the
    shape, the plain grid and two backward kernels."""
    fa = _fa()
    seq, blk, bq, chunk, packed, biased = _BD_CHUNKED[case]
    B, T, H, D = 2, 2 * seq, 2, 8
    q, k, v, tgt = (jnp.asarray(rng.standard_normal((B, T, H, D)),
                                jnp.float32) for _ in range(4))
    seg = (jnp.asarray(np.sort(rng.integers(0, 3, (B, T)), axis=1),
                       jnp.int32) if packed else None)
    bias = (jnp.asarray(rng.standard_normal((B, T)), jnp.float32)
            if biased else None)
    tiles = (bq, T, bq, T, chunk, chunk)

    def loss_flash(q, k, v, bias):
        o = fa._attend(q, k, v, False, D ** -0.5, bias, seg, tiles, 0,
                       (seq, blk))
        return jnp.mean((o - tgt) ** 2)

    def loss_dense(q, k, v, bias):
        return _bd_dense_loss(q, k, v, tgt, seq, blk, seg, bias)

    argnums = (0, 1, 2, 3) if biased else (0, 1, 2)
    text = str(jax.make_jaxpr(jax.grad(loss_flash, argnums))(q, k, v, bias))
    plain = case in _BD_PLAIN
    assert text.count("while[") == (0 if plain else 6)
    assert _kernel_calls(text)["flash_dq"] == (1 if plain or biased else 0)
    lf, gf = jax.value_and_grad(loss_flash, argnums)(q, k, v, bias)
    ld, gd = jax.value_and_grad(loss_dense, argnums)(q, k, v, bias)
    np.testing.assert_allclose(float(lf), float(ld), rtol=1e-5)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-5)


@pytest.mark.parametrize("seq,blk,bq,chunk", [
    (64, 4, 16, 16), (64, 4, 32, 16), (64, 4, 16, 32), (48, 3, 16, 16),
    (96, 6, 32, 16), (64, 16, 16, 16), (64, 32, 16, 16), (64, 8, 64, 32),
    (120, 5, 40, 24), (512, 4, 64, 128)])
def test_bd_chunks_are_what_the_dense_mask_shows(seq, blk, bq, chunk):
    """``_bd_chunks``: against each Q tile the union of its runs is exactly
    the chunks that hold a visible pair, no chunk is in two runs, and every
    chunk of a run that takes no mask is visible whole. The same function on
    traced scalars (as in a kernel) gives the same runs, and ``bd_tiles``
    counts them."""
    fa = _fa()
    mask = _bd_visible(seq, blk)
    nq, nc = 2 * seq // bq, 2 * seq // chunk
    tiles = mask.reshape(nq, bq, nc, chunk)
    some, whole = tiles.any(axis=(1, 3)), tiles.all(axis=(1, 3))
    # a run's bounds for every Q tile at once (a bound may be one number)
    runs = [(np.broadcast_to(lo, nq), np.broadcast_to(hi, nq), crossed)
            for lo, hi, crossed in fa._bd_chunks(np.arange(nq), bq, chunk,
                                                 seq, blk, xp=np)]
    traced = jax.jit(lambda i: [(lo, hi) for lo, hi, _ in
                                fa._bd_chunks(i, bq, chunk, seq, blk)])
    assert [crossed for _, _, crossed in runs] == [True, False, True]
    for i in range(nq):
        visits = np.zeros(nc, int)
        for lo, hi, crossed in runs:
            assert 0 <= lo[i] <= hi[i] <= nc
            visits[lo[i]:hi[i]] += 1
            if not crossed:
                assert whole[i, lo[i]:hi[i]].all(), (i, lo[i], hi[i])
        np.testing.assert_array_equal(visits, some[i].astype(int))
        assert [(int(lo), int(hi)) for lo, hi in traced(i)] == [
            (lo[i], hi[i]) for lo, hi, _ in runs]
    assert fa.bd_tiles(seq, blk, bq, 2 * seq, chunk) == (int(some.sum()),
                                                          nq * nc)


@pytest.mark.parametrize("block_q,block_k,chunk,visited,total", [
    (1024, 1024, None, 24, 64),         # the plain grid of PR 27: 37.5 %
    (256, 8192, 512, 160, 512),         # 31.25 %
    (512, 8192, 512, 80, 256),          # 31.25 %
    (256, 8192, 256, 288, 1024),        # 28.1 %
    (512, 8192, 1024, 48, 128),         # 37.5 %: chunks of 1,024 save none
])
def test_bd_tiles_at_the_cells_shape(block_q, block_k, chunk, visited, total):
    """8,192 positions in blocks of 4, head 128 in bfloat16: what
    ``bd_tiles_visited`` / ``bd_tiles_total`` say at the tilings the sweep
    tries, against a brute count over the dense mask (of which a quarter
    is visible)."""
    fa = _fa()
    seq, blk = 4096, 4
    mask = _bd_visible(seq, blk)
    assert mask.mean() == pytest.approx(0.25, abs=1e-3)
    c = chunk or block_k
    seen = mask.reshape(2 * seq // block_q, block_q, 2 * seq // c, c).any(
        axis=(1, 3))
    assert (int(seen.sum()), seen.size) == (visited, total)
    assert fa.bd_tiles(seq, blk, block_q, block_k, chunk, d=128,
                       itemsize=2) == (visited, total)


@pytest.mark.parametrize("seq,blk,bq,chunk,dtype,D", [
    (64, 4, 16, 16, "float32", 16), (96, 6, 32, 16, "float32", 16),
    (128, 4, 64, 128, "bfloat16", 64), (64, 4, 16, 32, "float32", 16)])
def test_one_kernel_bd_backward_equals_the_two_kernel_one(rng, seq, blk, bq,
                                                          chunk, dtype, D):
    """Under the block-diffusion mask too the dK/dV kernel's loop sums dQ
    and no ``flash_dq`` call is made; a tracked bias gradient brings the
    two-kernel backward of the same tiles back (``flash_dq`` with the same
    loop): dQ bit for bit, dK and dV summed in another order."""
    fa = _fa()
    B, H, T = 1, 2, 2 * seq
    bd = (seq, blk)
    q, k, v, do = (jnp.asarray(rng.standard_normal((B * H, T, D)), dtype)
                   for _ in range(4))
    scale = D ** -0.5

    def backward(bias, want_db):
        o, lse = fa._fwd(q, k, v, bias, None, None, H, scale, False, bq, T,
                         bd=bd, chunk=chunk)

        def bwd(q, k, v, o, lse, do):
            return fa._bwd(H, scale, False, bq, T,
                           (q, k, v, bias, None, None, o, lse), do,
                           want_db=want_db, bd=bd, chunk=chunk)[:3]
        calls = _kernel_calls(str(jax.make_jaxpr(bwd)(q, k, v, o, lse, do)))
        return calls, bwd(q, k, v, o, lse, do)

    calls, one = backward(None, want_db=False)
    assert calls == {"flash_fwd": 0, "flash_dq": 0, "flash_dkv": 1}
    calls, two = backward(jnp.zeros((B, T, 1), jnp.float32), want_db=True)
    assert calls == {"flash_fwd": 0, "flash_dq": 1, "flash_dkv": 1}
    as32 = lambda x: np.asarray(x.astype(jnp.float32))
    np.testing.assert_array_equal(as32(one[0]), as32(two[0]))
    tol = dict(rtol=2e-2, atol=2e-2) if dtype == "bfloat16" \
        else dict(rtol=1e-5, atol=1e-5)
    for a, b in zip(one[1:], two[1:]):
        np.testing.assert_allclose(as32(a), as32(b), **tol)


def test_an_unmasked_bd_chunk_skips_the_masks_arithmetic(rng):
    """A chunk of the clean prefix that a Q tile sees whole goes through no
    ``block_diffusion_mask``: of the forward's three runs of chunks two hold
    the mask's shifts, one holds none."""
    fa = _fa()
    x = jnp.zeros((1, 128, 2, 8), jnp.float32)
    tiles = (16, 128, 16, 128, 16, 16)
    text = str(jax.make_jaxpr(lambda q: fa._attend(
        q, q, q, False, 1.0, None, None, tiles, 0, (64, 4)))(x))
    loops = text.split("while[")[1:]
    assert len(loops) == 3
    assert ["shift_right_logical" in body for body in loops] == [
        True, False, True]


_BD_FALLBACKS = {
    # name -> (table entry's (block_q, block_k, chunk), (seq, block_len),
    # head_dim): a block-diffusion call by the public API whose shape the
    # loop cannot take
    "no_chunk_in_the_entry": ((32, 32, None), (64, 4), 16),
    "seq_no_whole_chunks": ((16, 128, 32), (48, 4), 16),
    "q_tile_over_the_seam": ((32, 128, 16), (48, 4), 16),
}


@pytest.mark.parametrize("case", ["looped"] + list(_BD_FALLBACKS))
def test_a_bd_call_the_loop_cannot_take_runs_the_plain_grid(
        monkeypatch, tmp_path, case):
    """``flash_attention(block_diffusion=)`` by the table alone: with an
    entry that carries a chunk the call loops and its backward is one
    kernel; without a chunk, with a half that is no whole number of
    chunks, or with a Q tile over the seam between the halves, it runs the
    plain grid and two backward kernels, as before."""
    from horovod_tpu.ops import tile_table
    fa = _fa()
    (bq, bk, chunk), (seq, blk), D = _BD_FALLBACKS.get(
        case, ((16, 128, 16), (64, 4), 16))
    p = tmp_path / "t.json"
    extra = {} if chunk is None else dict(chunk=chunk, chunk_bwd=chunk)
    tile_table.record(D, 2 * seq, "float32", "block_diffusion", bq, bk,
                      source="test", path=p, block_q_bwd=bq, block_k_bwd=bk,
                      **extra)
    monkeypatch.setenv("HOROVOD_FLASH_TILE_TABLE", str(p))
    monkeypatch.setattr(fa, "_use_interpret", lambda: False)
    x = jnp.zeros((1, 2 * seq, 2, D), jnp.float32)
    text = str(jax.make_jaxpr(jax.grad(lambda q, k, v: jnp.sum(
        fa.flash_attention(q, k, v, block_diffusion=(seq, blk))),
        argnums=(0, 1, 2)))(x, x, x))
    looped = case == "looped"
    assert _kernel_calls(text) == {"flash_fwd": 1, "flash_dkv": 1,
                                   "flash_dq": 0 if looped else 1}
    assert text.count("while[") == (6 if looped else 0)


def test_a_bd_row_too_long_for_a_cores_vmem_runs_the_plain_grid():
    """The fourth way out: where the resident K tile of a block-diffusion
    call would take more VMEM than a core can give, ``_tiling`` hands back
    the grid with the chunk as its K tile, as for the causal mask; at the
    cell's shape (head 128, 8,192 positions) both kernels fit."""
    fa = _fa()
    for kernel, t in (("fwd", 131072), ("dkv", 65536)):
        shape = dict(d=128, itemsize=2, kernel=kernel)
        assert fa._tiling(8192, 8192, 512, 8192, 512, False, (4096, 4),
                          **shape) == (512, 8192, 512)
        assert fa._tiling(t, t, 512, t, 512, False, (t // 2, 4),
                          **shape) == (512, 512, None)
    assert fa._vmem_need("dkv", 512, 8192, 512, 128, 2,
                         extra="dq") <= fa._VMEM_CAP
    assert fa._vmem_need("dkv", 512, 65536, 512, 128, 2,
                         extra="dq") > fa._VMEM_CAP
    # and the other three, by the same function
    assert fa._tiling(8192, 8192, 1024, 1024, None, False, (4096, 4),
                      d=128, itemsize=2) == (1024, 1024, None)
    assert fa._tiling(8000, 8000, 256, 8192, 512, False, (4000, 4), d=128,
                      itemsize=2) == (256, 512, None)
    assert fa._tiling(8192, 8192, 3072, 8192, 512, False, (4096, 4), d=128,
                      itemsize=2) == (3072, 512, None)
