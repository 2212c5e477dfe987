"""Load a HuggingFace checkpoint, fine-tune a step, generate — the
migration loop end-to-end (convert -> train -> decode).

Uses a random-init HF model (this image has no network for pretrained
downloads); with connectivity, `GPT2LMHeadModel.from_pretrained("gpt2")`
drops in unchanged. The demo proves the loop the way the test suite
does: our greedy decode matches HF `generate()` token-for-token on the
same weights, then one fine-tune step shifts the continuation.

Run:
  JAX_PLATFORMS=cpu python examples/hf_generate.py
"""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import jax
import jax.numpy as jnp
import numpy as np
import optax

import horovod_tpu as hvd
from horovod_tpu.models.convert import gpt2_from_hf
from horovod_tpu.models.generate import generate
from horovod_tpu.models.gpt2 import loss_fn


def main():
    import torch
    import transformers

    hvd.init()
    torch.manual_seed(0)
    hf = transformers.GPT2LMHeadModel(transformers.GPT2Config(
        vocab_size=256, n_positions=128, n_embd=64, n_layer=2,
        n_head=4)).eval()
    model, params = gpt2_from_hf(hf)

    rng = np.random.default_rng(0)
    prompt = rng.integers(1, 256, (2, 8))

    # 1. Parity: same weights, same greedy continuation as HF.
    with torch.no_grad():
        theirs = hf.generate(torch.from_numpy(prompt), max_new_tokens=12,
                             do_sample=False, pad_token_id=0).numpy()
    ours = np.asarray(generate(model, params,
                               jnp.asarray(prompt, jnp.int32), 12))
    assert (ours == theirs).all(), "greedy decode diverged from HF"
    print(f"greedy decode == hf.generate over {ours.shape[1]} tokens")

    # 2. Fine-tune one step on a synthetic batch...
    toks = jnp.asarray(rng.integers(1, 256, (4, 32)), jnp.int32)
    opt = hvd.DistributedOptimizer(optax.adamw(1e-2))
    ost = opt.init(params)

    @jax.jit
    def step(p, ost):
        l, g = jax.value_and_grad(
            lambda p: loss_fn(model.apply({"params": p}, toks), toks))(p)
        u, ost = opt.update(g, ost, p)
        return optax.apply_updates(p, u), ost, l

    params2, ost, l = step(jax.tree_util.tree_map(jnp.asarray, params),
                           ost)
    print(f"fine-tune step: loss {float(l):.4f}")

    # 3. ...and sample from the updated weights.
    sampled = generate(model, params2, jnp.asarray(prompt, jnp.int32), 12,
                       temperature=0.8, top_k=40,
                       rng=jax.random.PRNGKey(0))
    print(f"sampled continuation (post-finetune): "
          f"{np.asarray(sampled)[0, 8:].tolist()}")


if __name__ == "__main__":
    main()
