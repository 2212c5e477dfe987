"""What ``remat_policy`` saves (``models/remat.py``), on the CPU with
interpreted kernels: under ``dots`` the flash forward's named output and
log-sum-exp are kept, so a gradient holds one forward kernel call a layer
and not two; ``full`` keeps nothing; every family builds its blocks with
the one helper."""

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import bert, gpt2, llama, sdar, t5
from horovod_tpu.models.remat import remat_block

KERNELS = ("flash_fwd", "flash_dq", "flash_dkv")


def _tokens(shape=(2, 32), vocab=256, seed=1):
    return jnp.asarray(
        np.random.default_rng(seed).integers(0, vocab, shape), jnp.int32)


def _gpt2_loss(**kw):
    cfg = dataclasses.replace(
        gpt2.GPT2Config.tiny(attention="flash", dtype=jnp.float32), **kw)
    model, toks = gpt2.GPT2(cfg), _tokens()
    params = model.init(jax.random.PRNGKey(0), toks)
    return (lambda p: gpt2.loss_fn(model.apply(p, toks), toks)), params


@pytest.mark.parametrize("kw,forwards", [
    (dict(remat=False), 2),
    (dict(remat=True, remat_policy="dots"), 2),
    (dict(remat=True, remat_policy="full"), 4),
], ids=["none", "dots", "full"])
def test_forward_kernel_calls_in_a_two_layer_gradient(kw, forwards):
    """Two layers: ``dots`` keeps what the forward kernel wrote and its
    backward runs it no second time (4 calls before the residuals had
    names); ``full`` asked for the least memory and still pays twice."""
    loss, params = _gpt2_loss(**kw)
    text = str(jax.make_jaxpr(jax.grad(loss))(params))
    calls = {k: text.count(f"name={k}") for k in KERNELS}
    assert calls == {"flash_fwd": forwards, "flash_dq": 2, "flash_dkv": 2}


# ---------------------------------------------------------------------------
# the five families build their blocks with the one helper
# ---------------------------------------------------------------------------

def _family_llama(**kw):
    cfg = llama.LlamaConfig.tiny(dtype=jnp.float32, attention="flash",
                                 flash_blocks=(16, 16), **kw)
    model, toks = llama.Llama(cfg), _tokens()
    params = model.init(jax.random.PRNGKey(0), toks)
    return (lambda p: llama.loss_fn(model.apply(p, toks), toks)), params


def _family_bert(**kw):
    cfg = dataclasses.replace(bert.BertConfig.tiny(), dtype=jnp.float32,
                              attention="flash", **kw)
    model, toks = bert.Bert(cfg), _tokens()
    picked = jnp.zeros(toks.shape).at[:, :3].set(1.0)
    # a padded tail: the kernel's key bias is part of what is differentiated
    keep = jnp.ones(toks.shape, bool).at[:, -5:].set(False)
    params = model.init(jax.random.PRNGKey(0), toks, attention_mask=keep)
    return (lambda p: bert.mlm_loss(
        model.apply(p, toks, attention_mask=keep)[0], toks, picked)), params


def _family_t5(**kw):
    cfg = t5.T5Config.tiny(dtype=jnp.float32, **kw)     # dense only
    model, src, tgt = t5.T5(cfg), _tokens((2, 16)), _tokens((2, 8), seed=2)
    params = model.init(jax.random.PRNGKey(0), src, tgt)["params"]
    return (lambda p: t5.seq2seq_loss(model, p, src, tgt)), params


def _family_sdar(**kw):
    cfg = sdar.SDARConfig.tiny(experts_held=(2, 2), top_k=4,
                               dtype=jnp.float32, attention="flash",
                               flash_blocks=(16, 32), **kw)
    model, toks = sdar.SDAR(cfg), _tokens()
    noise = sdar.block_noise(jax.random.split(jax.random.PRNGKey(1), 2),
                             toks.shape[1], cfg.block_len)
    params = model.init(jax.random.PRNGKey(0), toks, toks)["params"]
    return (lambda p: sdar.loss_fn(model, p, toks, noise)), params


FAMILIES = {"gpt2": _gpt2_loss, "llama": _family_llama,
            "bert": _family_bert, "t5": _family_t5, "sdar": _family_sdar}


@pytest.fixture(scope="module")
def plain_gradients():
    """{family: the gradient without remat}, each computed once."""
    kept = {}

    def of(family):
        if family not in kept:
            loss, params = FAMILIES[family](remat=False)
            kept[family] = jax.jit(jax.grad(loss))(params)
        return kept[family]
    return of


@pytest.mark.parametrize("policy", ["dots", "full", "everything"])
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_family_blocks_under_the_policy(plain_gradients, family, policy):
    """``dots`` and ``full`` change what the backward runs again and never
    the gradient (flash attention wherever the family has it, so that the
    kept residuals are what the backward kernels read); a name that is
    neither raises, as it always did."""
    if policy == "everything":
        with pytest.raises(ValueError, match="unknown remat_policy"):
            FAMILIES[family](remat=True, remat_policy=policy)
        return
    loss, params = FAMILIES[family](remat=True, remat_policy=policy)
    got = jax.jit(jax.grad(loss))(params)
    # 4e-3: test_models.py::test_remat_policy_grads_match's headroom (on
    # the CPU remat alone moves the last bits, under "full" too).
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(plain_gradients(family))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=4e-3, atol=4e-3)


def test_helper_leaves_a_block_alone_without_remat():
    class Cfg:
        remat, remat_policy = False, "everything"   # never looked at
    assert remat_block(nn.Dense, Cfg) is nn.Dense
    Cfg.remat = True
    with pytest.raises(ValueError, match="expected 'full' or 'dots'"):
        remat_block(nn.Dense, Cfg)
