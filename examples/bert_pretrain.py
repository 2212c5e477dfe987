"""BERT-large MLM+NSP pretraining — mirrors the reference benchmark config
"BERT-large pretraining (TF2 DistributedGradientTape + Adasum)" on the JAX
frontend: DistributedGradientTape-style grad sync with the Adasum reduction,
flash attention, and the sharded data pipeline (synthetic corpus: no
datasets ship in the image).

Run single-host:      python examples/bert_pretrain.py
Virtual 8-dev CPU:    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
                      JAX_PLATFORMS=cpu python examples/bert_pretrain.py
"""

import dataclasses
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.data import ShardedBatchIterator
from horovod_tpu.models.bert import Bert, BertConfig


def main(steps: int = 20, batch_per_rank: int = 4, seq_len: int = 64,
         tiny: bool = True):
    hvd.init()
    n = hvd.size()
    print(f"communicator: size={n} backend={jax.default_backend()}")

    cfg = BertConfig.tiny() if tiny else BertConfig.large()
    if jax.default_backend() == "tpu":
        cfg = dataclasses.replace(cfg, attention="flash")
    model = Bert(cfg)

    # Synthetic corpus, sharded per rank by the data pipeline.
    rng = np.random.default_rng(0)
    n_docs = steps * batch_per_rank * n
    corpus_tokens = rng.integers(4, cfg.vocab_size, (n_docs, seq_len))
    corpus_types = np.zeros_like(corpus_tokens)
    corpus_nsp = rng.integers(0, 2, (n_docs,))

    tokens0 = jnp.zeros((batch_per_rank, seq_len), jnp.int32)
    mask0 = jnp.ones((batch_per_rank, seq_len), bool)
    variables = model.init(jax.random.PRNGKey(0), tokens0, tokens0, mask0)
    params = variables["params"]

    # Adasum reduction (the reference's BERT recipe): scale-free gradient
    # combining that tolerates large effective batch sizes.
    opt = hvd.DistributedOptimizer(optax.adamw(1e-4), op=hvd.Adasum)
    opt_state = opt.init(params)

    def train_step(params, opt_state, tokens, types, nsp_labels):
        params = hvd.broadcast_parameters(params, root_rank=0)
        mask = jnp.ones_like(tokens, bool)

        def loss_fn(p):
            # MLM: replace ~1/7 of input positions with [MASK] (id 3) and
            # score the original tokens there, + NSP.
            mlm_pos = jnp.arange(tokens.shape[1]) % 7 == 0
            masked_tokens = jnp.where(mlm_pos[None], 3, tokens)
            mlm_logits, nsp_logits = model.apply(
                {"params": p}, masked_tokens, types, mask)
            logp = jax.nn.log_softmax(mlm_logits.astype(jnp.float32))
            mlm_ll = jnp.take_along_axis(logp, tokens[..., None], -1)[..., 0]
            mlm_loss = -jnp.mean(jnp.where(mlm_pos[None], mlm_ll, 0.0))
            nsp_lp = jax.nn.log_softmax(nsp_logits.astype(jnp.float32))
            nsp_loss = -jnp.mean(
                jnp.take_along_axis(nsp_lp, nsp_labels[:, None], -1))
            return mlm_loss + nsp_loss

        loss, grads = hvd.value_and_grad(loss_fn)(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    step = hvd.spmd(train_step,
                    in_specs=(P(), P(), P("hvd"), P("hvd"), P("hvd")),
                    out_specs=(P(), P(), P()))

    data = ShardedBatchIterator(
        [corpus_tokens, corpus_types, corpus_nsp],
        batch_size=batch_per_rank * n, rank=0, size=1, seed=0)
    for i, ((tokens, types, nsp), _mask) in enumerate(data):
        params, opt_state, loss = step(
            params, opt_state,
            jnp.asarray(tokens, jnp.int32), jnp.asarray(types, jnp.int32),
            jnp.asarray(nsp, jnp.int32))
        if i % 5 == 0:
            print(f"step {i}: loss={float(loss):.4f}")
        if i + 1 >= steps:
            break
    print(f"final loss={float(loss):.4f}")


if __name__ == "__main__":
    main()
