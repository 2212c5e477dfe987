"""T5 encoder-decoder (models/t5.py): relative-position buckets,
cross-attention over a padded source, seq2seq teacher forcing. Completes
the zoo's architecture coverage next to the decoder-only and
encoder-only families (upstream role: horovod/examples model scripts)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.models.t5 import (T5, T5Config, partition_rules,
                                   relative_position_bucket, seq2seq_loss,
                                   shift_right)


class TestBuckets:
    def test_bidirectional_splits_sign(self):
        rel = jnp.asarray([-5, -1, 0, 1, 5])
        b = relative_position_bucket(rel, bidirectional=True,
                                     num_buckets=8, max_distance=32)
        half = 4
        assert (np.asarray(b[:3]) < half).all()     # rel <= 0 low half
        assert (np.asarray(b[3:]) >= half).all()    # rel > 0 high half

    def test_causal_maps_future_to_zero(self):
        rel = jnp.asarray([3, 1, 0, -1, -3])
        b = relative_position_bucket(rel, bidirectional=False,
                                     num_buckets=8, max_distance=32)
        assert int(b[0]) == 0 and int(b[1]) == 0    # future collapsed
        assert int(b[2]) == 0
        assert int(b[3]) == 1                        # exact small buckets
        assert int(b[4]) == 3

    def test_log_buckets_saturate(self):
        rel = -jnp.asarray([1, 4, 16, 64, 10_000])
        b = np.asarray(relative_position_bucket(
            rel, bidirectional=False, num_buckets=8, max_distance=32))
        assert (np.diff(b) >= 0).all()               # monotone
        assert b[-1] == 7                            # saturates at n-1
        assert b[-2] == 7                            # beyond max_distance


class TestT5Model:
    def _setup(self, rng, **cfg_kw):
        cfg = T5Config.tiny(**cfg_kw)
        model = T5(cfg)
        src = jnp.asarray(rng.integers(1, cfg.vocab_size, (2, 24)),
                          jnp.int32)
        tgt = jnp.asarray(rng.integers(1, cfg.vocab_size, (2, 16)),
                          jnp.int32)
        params = model.init(jax.random.PRNGKey(0), src,
                            shift_right(tgt, cfg.pad_id))["params"]
        return cfg, model, src, tgt, params

    def test_forward_shape(self, rng):
        cfg, model, src, tgt, params = self._setup(rng)
        logits = model.apply({"params": params}, src,
                             shift_right(tgt, cfg.pad_id))
        assert logits.shape == (2, 16, cfg.vocab_size)
        assert logits.dtype == jnp.float32

    def test_one_bias_table_per_stack(self, rng):
        cfg, model, src, tgt, params = self._setup(rng)
        paths = ["/".join(str(k.key) for k in kp) for kp, _ in
                 jax.tree_util.tree_flatten_with_path(params)[0]]
        bias_paths = sorted(p for p in paths if "rel_bias" in p)
        # Exactly two tables in the WHOLE tree — one per stack, none
        # inside any layer (incl. cross-attention).
        assert bias_paths == ["dec_rel/rel_bias", "enc_rel/rel_bias"], \
            bias_paths

    def test_source_padding_is_invisible(self, rng):
        """Padding the source (with mask) must not change the logits —
        cross-attention and encoder self-attention both mask it."""
        cfg, model, src, tgt, params = self._setup(rng)
        dec_in = shift_right(tgt, cfg.pad_id)
        base = model.apply({"params": params}, src, dec_in)
        pad = jnp.full((2, 8), cfg.pad_id, jnp.int32)
        src_padded = jnp.concatenate([src, pad], axis=1)
        got = model.apply({"params": params}, src_padded, dec_in)
        np.testing.assert_allclose(np.asarray(got), np.asarray(base),
                                   rtol=2e-2, atol=2e-2)

    def test_decoder_is_causal(self, rng):
        """Changing a LATER decoder input must not affect earlier
        positions' logits."""
        cfg, model, src, tgt, params = self._setup(rng)
        dec_in = shift_right(tgt, cfg.pad_id)
        base = model.apply({"params": params}, src, dec_in)
        mutated = dec_in.at[:, 10:].set(7)
        got = model.apply({"params": params}, src, mutated)
        np.testing.assert_allclose(np.asarray(got[:, :10]),
                                   np.asarray(base[:, :10]),
                                   rtol=1e-5, atol=1e-5)

    def test_trains(self, rng):
        cfg, model, src, tgt, params = self._setup(rng)
        opt = optax.adam(1e-2)
        ost = opt.init(params)

        @jax.jit
        def step(params, ost):
            l, g = jax.value_and_grad(
                lambda p: seq2seq_loss(model, p, src, tgt))(params)
            u, ost = opt.update(g, ost, params)
            return optax.apply_updates(params, u), ost, l

        first = last = None
        for _ in range(10):
            params, ost, l = step(params, ost)
            last = float(l)
            first = first if first is not None else last
        assert last < 0.7 * first, (first, last)

    def test_all_padding_source_row_yields_finite_logits(self, rng):
        """A batch row whose source is ENTIRELY padding must not poison
        the decoder (the shared dense path zeroes fully-masked attention
        rows instead of softmaxing over -inf)."""
        cfg, model, src, tgt, params = self._setup(rng)
        src_dead = src.at[0].set(cfg.pad_id)       # row 0: all pads
        dec_in = shift_right(tgt, cfg.pad_id)
        logits = model.apply({"params": params}, src_dead, dec_in)
        assert np.isfinite(np.asarray(logits)).all()
        # ...and the healthy row is untouched by its neighbour's padding
        base = model.apply({"params": params}, src, dec_in)
        np.testing.assert_allclose(np.asarray(logits[1]),
                                   np.asarray(base[1]), rtol=1e-5,
                                   atol=1e-5)

    def test_pad_labels_carry_no_loss(self, rng):
        cfg, model, src, tgt, params = self._setup(rng)
        # padding the TARGET tail must leave the loss unchanged
        l1 = seq2seq_loss(model, params, src, tgt)
        tgt_padded = jnp.concatenate(
            [tgt, jnp.full((2, 6), cfg.pad_id, jnp.int32)], axis=1)
        l2 = seq2seq_loss(model, params, src, tgt_padded)
        np.testing.assert_allclose(float(l1), float(l2), rtol=2e-2)

    def test_tp_sharded_step_matches_single_device(self, rng):
        """dp x tp GSPMD training step == single-device step (the same
        parity bar every other zoo family meets)."""
        from horovod_tpu.parallel import make_mesh, shard_pytree
        from jax.sharding import NamedSharding

        cfg, model, src, tgt, params = self._setup(rng)

        def grads(p):
            return jax.grad(
                lambda p: seq2seq_loss(model, p, src, tgt))(p)

        ref = jax.jit(grads)(params)

        mesh = make_mesh({"dp": 2, "tp": 4})
        sharded = shard_pytree(params, mesh, partition_rules())
        s_src = jax.device_put(src, NamedSharding(mesh, P("dp")))
        s_tgt = jax.device_put(tgt, NamedSharding(mesh, P("dp")))
        with jax.set_mesh(mesh):
            got = jax.jit(lambda p: jax.grad(
                lambda p: seq2seq_loss(model, p, s_src, s_tgt))(p)
            )(sharded)
        # bf16 compute: tp-split matmuls change accumulation order, so
        # individual near-zero grads can wobble by ~1e-2 absolute.
        for a, b in zip(jax.tree_util.tree_leaves(ref),
                        jax.tree_util.tree_leaves(got)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-2, atol=1e-2)

    def test_partition_rules_cover_real_paths(self, rng):
        cfg, model, src, tgt, params = self._setup(rng)
        rules = partition_rules()
        paths = ["/".join(str(k.key) for k in kp) for kp, _ in
                 jax.tree_util.tree_flatten_with_path(params)[0]]
        q_paths = [p for p in paths if p.endswith("q/kernel")]
        assert q_paths
        for p in q_paths:
            assert rules.spec_for(p) == P(None, "tp"), p
        assert rules.spec_for("embedding") == P("tp", None)
