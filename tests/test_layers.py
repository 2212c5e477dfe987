"""The decoder parts that the training families share (``models/layers.py``),
on the CPU at the families' tiny sizes: the grouped-query attention under
each family's mask and latent attention under both of its layouts write the
parameters the families' own attention modules wrote, by name and shape;
the next-token head is the gather from ``log_softmax`` that it stands in
for."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import glm4_moe_lite as glm
from horovod_tpu.models import kimi_linear as kl
from horovod_tpu.models import lfm2, sdar
from horovod_tpu.models import smallthinker as st
from horovod_tpu.models.layers import next_token_cross_entropy


def _shapes(model, cfg):
    tokens = jnp.zeros((1, 8), jnp.int32)
    args = (tokens, tokens) if model is sdar.SDAR else (tokens,)
    params = jax.eval_shape(
        lambda: model(cfg).init(jax.random.PRNGKey(0), *args)["params"])
    return jax.tree_util.tree_map(lambda x: tuple(x.shape), params)


# (mask, family, its tiny config, the block whose attention has that mask,
#  whether the family norms every query and key head)
_GQA = [("block_diffusion", sdar.SDAR, sdar.SDARConfig, 0, True),
        ("causal", lfm2.LFM2, lfm2.LFM2Config, 1, True),
        ("global", st.SmallThinker, st.SmallThinkerConfig, 0, False),
        ("window", st.SmallThinker, st.SmallThinkerConfig, 1, False)]


@pytest.mark.parametrize("mask, model, config, layer, qk_norm", _GQA,
                         ids=[g[0] for g in _GQA])
def test_grouped_query_attention_writes_the_familys_parameters(
        mask, model, config, layer, qk_norm):
    cfg = config.tiny()
    d, H, Hkv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    want = {"wq": {"kernel": (d, H * hd)}, "wk": {"kernel": (d, Hkv * hd)},
            "wv": {"kernel": (d, Hkv * hd)}, "wo": {"kernel": (H * hd, d)}}
    if qk_norm:
        want.update(q_norm={"scale": (hd,)}, k_norm={"scale": (hd,)})
    assert _shapes(model, cfg)[f"h{layer}"]["attn"] == want
    if model is st.SmallThinker:
        attn = st.attention(cfg, layer)
        assert isinstance(attn, st.Attention)
        assert (attn.window is None) == (mask == "global")
        assert (attn.rope_theta is None) == (mask == "global")


# (family, its tiny config, the block of latent attention, the query's
#  parameters beside kv_a, kv_norm, kv_b and o)
_MLA = [("bottleneck_rope", glm.Glm4MoeLite, glm.Glm4MoeLiteConfig, 0,
         {"q_a", "q_norm", "q_b"}),
        ("direct_nope", kl.KimiLinear, kl.KimiLinearConfig, 3, {"q"})]


@pytest.mark.parametrize("layout, model, config, layer, query", _MLA,
                         ids=[m[0] for m in _MLA])
def test_latent_attention_writes_the_familys_parameters(layout, model, config,
                                                        layer, query):
    cfg = config.tiny()
    got = _shapes(model, cfg)[f"h{layer}"]["attn"]
    assert set(got) == query | {"kv_a", "kv_norm", "kv_b", "o"}
    H, rank = cfg.num_heads, cfg.kv_lora_rank
    nope, rope, dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                      cfg.v_head_dim)
    assert got["kv_a"]["kernel"] == (cfg.d_model, rank + rope)
    assert got["kv_b"]["kernel"] == (rank, H * (nope + dv))
    assert got["o"]["kernel"] == (H * dv, cfg.d_model)
    if "q" in query:
        assert got["q"]["kernel"] == (cfg.d_model, H * (nope + rope))
    else:
        assert got["q_b"]["kernel"] == (cfg.q_lora_rank, H * (nope + rope))


@pytest.mark.parametrize("ahead", [1, 2])
def test_next_token_head_is_the_log_softmax_gather(ahead):
    B, T, d, V = 2, 12, 8, 40
    k_h, k_w, k_t = jax.random.split(jax.random.PRNGKey(0), 3)
    hidden = jax.random.normal(k_h, (B, T, d), jnp.float32)
    head = jax.random.normal(k_w, (V, d), jnp.float32)
    tokens = jax.random.randint(k_t, (B, T), 0, V)
    got = next_token_cross_entropy(hidden, head, tokens, ahead)
    logits = np.einsum("btd,vd->btv", np.asarray(hidden, np.float64),
                       np.asarray(head, np.float64))
    logp = logits - np.log(np.exp(logits).sum(-1, keepdims=True))
    want = -np.take_along_axis(logp[:, :-ahead],
                               np.asarray(tokens)[:, ahead:, None], -1).mean()
    np.testing.assert_allclose(float(got), want, rtol=1e-5)
