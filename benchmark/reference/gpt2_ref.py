"""GPT-2 as published, in plain ``jax.numpy`` and float32: forward, loss and
gradients. The yardstick the system's outputs are held to; it shares no
code with ``horovod_tpu``.

Radford et al. 2019 / openai-community ``modeling_gpt2``: learned token and
position embeddings, pre-LayerNorm blocks (attention with one fused qkv
projection, MLP of width 4d with the tanh GELU ``gelu_new``), a final
LayerNorm and the output head tied to the token embedding. Departures: none
in the mathematics. The 24 blocks' leaves are stacked and applied with
``lax.scan`` over one checkpointed block, so the compiled program holds one
block and not 24 (the unrolled form took 130 s to compile on the v5e and
filled the compile cache, ISSUE 24). Matrix products run under
``default_matmul_precision("highest")``: on a TPU a float32 product is
otherwise rounded like bfloat16.
"""

import functools

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnames=("num_layers",))
def from_system(params, num_layers):
    """The system's flax tree (``wte``, ``wpe``, ``h<i>/...``, ``ln_f``)
    as the reference's: float32, the blocks stacked on a leading axis."""
    f32 = lambda x: jnp.asarray(x, jnp.float32)
    blocks = [params[f"h{i}"] for i in range(num_layers)]
    return {
        "wte": f32(params["wte"]), "wpe": f32(params["wpe"]),
        "ln_f": jax.tree_util.tree_map(f32, dict(params["ln_f"])),
        "h": jax.tree_util.tree_map(lambda *xs: jnp.stack(
            [f32(x) for x in xs]), *blocks),
    }


def _layer_norm(x, p, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _dense(x, p):
    return x @ p["kernel"] + p["bias"]


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        jnp.sqrt(2.0 / jnp.pi) * (x + 0.044715 * x ** 3)))


def _block(x, p, num_heads, eps):
    B, T, D = x.shape
    q, k, v = jnp.split(_dense(_layer_norm(x, p["ln1"], eps),
                               p["attn"]["qkv"]), 3, axis=-1)
    heads = lambda a: a.reshape(B, T, num_heads, D // num_heads)
    s = jnp.einsum("bqhd,bkhd->bhqk", heads(q), heads(k))
    s = s / jnp.sqrt(jnp.float32(D // num_heads))
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal, s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), heads(v))
    x = x + _dense(o.reshape(B, T, D), p["attn"]["out"])
    h = _gelu_new(_dense(_layer_norm(x, p["ln2"], eps), p["mlp"]["fc"]))
    return x + _dense(h, p["mlp"]["proj"])


def forward(ref, tokens, *, num_heads, eps):
    """Logits ``(B, T, V)`` of every position."""
    with jax.default_matmul_precision("highest"):
        T = tokens.shape[1]
        x = ref["wte"][tokens] + ref["wpe"][jnp.arange(T)]
        block = jax.checkpoint(
            lambda x, p: (_block(x, p, num_heads, eps), None))
        x, _ = jax.lax.scan(block, x, ref["h"])
        return _layer_norm(x, ref["ln_f"], eps) @ ref["wte"].T


def loss(ref, tokens, *, num_heads, eps):
    """Mean next-token cross entropy over ``tokens[:, 1:]``."""
    logits = forward(ref, tokens, num_heads=num_heads, eps=eps)[:, :-1]
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    return -ll.mean()


@functools.partial(jax.jit, static_argnames=("num_heads", "eps"),
                   donate_argnums=(1, 2))
def ref_microbatch(ref, loss_sum, grad_sum, tokens, *, num_heads, eps):
    """Add one micro-batch's loss and gradients to the running sums."""
    value, grads = jax.value_and_grad(loss)(
        ref, tokens, num_heads=num_heads, eps=eps)
    return loss_sum + value, jax.tree_util.tree_map(jnp.add, grad_sum, grads)


@jax.jit
def ref_norm(grad_sum, n):
    return jnp.sqrt(sum(jnp.sum((g / n) ** 2) for g in
                        jax.tree_util.tree_leaves(grad_sum)))


def loss_and_grad_norm(ref, tokens, *, num_heads, eps, micro=2):
    """Loss of the whole batch and the norm of its gradient, taken in
    micro-batches of ``micro`` sequences (equal sizes, so the batch mean is
    the mean of the micro-batch means)."""
    if tokens.shape[0] % micro:
        raise ValueError(f"batch {tokens.shape[0]} is no multiple of {micro}")
    n = tokens.shape[0] // micro
    loss_sum = jnp.zeros((), jnp.float32)
    grad_sum = jax.tree_util.tree_map(jnp.zeros_like, ref)
    for i in range(n):
        loss_sum, grad_sum = ref_microbatch(
            ref, loss_sum, grad_sum, tokens[i * micro:(i + 1) * micro],
            num_heads=num_heads, eps=eps)
    return float(loss_sum) / n, float(ref_norm(grad_sum, jnp.float32(n)))


@functools.partial(jax.jit, static_argnames=("num_heads", "eps"))
def ref_score(ref, seqs, *, num_heads, eps):
    """For every position t of ``seqs`` (B, L): the top logit for position
    t+1 and the logit of the token ``seqs[:, t+1]`` that follows. Two
    ``(B, L-1)`` arrays."""
    logits = forward(ref, seqs, num_heads=num_heads, eps=eps)[:, :-1]
    chosen = jnp.take_along_axis(logits, seqs[:, 1:, None], axis=-1)[..., 0]
    return logits.max(-1), chosen
