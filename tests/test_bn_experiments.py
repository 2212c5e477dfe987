"""ResNet BN/stem experiments (VERDICT r3 item 6): tunable-stats batch
norm and the space-to-depth stem, correctness-tested here. Both measured
negative on the chip in round 4 (ROADMAP "Closed — do not retry")."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.ops.batch_norm import TunableBatchNorm, space_to_depth


@pytest.fixture
def x(rng):
    return jnp.asarray(rng.standard_normal((8, 6, 6, 16)) * 2 + 1,
                       jnp.float32)


class TestTunableBatchNorm:
    def test_fp32_stats_match_flax(self, x):
        ref = nn.BatchNorm(use_running_average=False, momentum=0.9,
                           epsilon=1e-5, dtype=jnp.float32,
                           param_dtype=jnp.float32)
        got = TunableBatchNorm(use_running_average=False, momentum=0.9,
                               epsilon=1e-5, dtype=jnp.float32,
                               stats_dtype=jnp.float32)
        vr = ref.init(jax.random.PRNGKey(0), x)
        vg = got.init(jax.random.PRNGKey(0), x)
        # identical variable layout -> checkpoint compatible
        assert jax.tree_util.tree_structure(vr) == \
            jax.tree_util.tree_structure(vg)
        yr, sr = ref.apply(vr, x, mutable=["batch_stats"])
        yg, sg = got.apply(vr, x, mutable=["batch_stats"])  # SAME vars
        np.testing.assert_allclose(np.asarray(yg), np.asarray(yr),
                                   rtol=1e-5, atol=1e-5)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6),
            sg, sr)

    def test_eval_uses_running_stats(self, x):
        bn = TunableBatchNorm(use_running_average=True)
        v = bn.init(jax.random.PRNGKey(0), x)
        v = jax.tree_util.tree_map(lambda a: a, v)
        y = bn.apply(v, x)
        # running stats are zeros/ones at init -> identity modulo eps
        np.testing.assert_allclose(np.asarray(y), np.asarray(x),
                                   rtol=1e-3, atol=1e-3)

    def test_bf16_stats_approximate_fp32(self, x):
        f32 = TunableBatchNorm(use_running_average=False,
                               stats_dtype=jnp.float32,
                               dtype=jnp.float32)
        b16 = TunableBatchNorm(use_running_average=False,
                               stats_dtype=jnp.bfloat16,
                               dtype=jnp.float32)
        v = f32.init(jax.random.PRNGKey(1), x)
        y32, _ = f32.apply(v, x, mutable=["batch_stats"])
        y16, _ = b16.apply(v, x, mutable=["batch_stats"])
        # bf16 moment rounding: same answer to ~1e-2 on unit-scale data
        np.testing.assert_allclose(np.asarray(y16), np.asarray(y32),
                                   rtol=0.15, atol=0.15)

    def test_cross_replica_stats_match_full_batch(self, x):
        """axis_name pmean: per-shard moments averaged over the mesh equal
        full-batch moments (sync BN semantics)."""
        bn_local = TunableBatchNorm(use_running_average=False,
                                    dtype=jnp.float32)
        v = bn_local.init(jax.random.PRNGKey(2), x)
        want, _ = bn_local.apply(v, x, mutable=["batch_stats"])

        bn_sync = TunableBatchNorm(use_running_average=False,
                                   dtype=jnp.float32, axis_name="hvd")

        def body(x):
            y, _ = bn_sync.apply(v, x, mutable=["batch_stats"])
            return y

        fn = hvd.spmd(body, in_specs=P("hvd"), out_specs=P("hvd"))
        got = fn(x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)


class TestSpaceToDepthStem:
    def test_space_to_depth_layout(self):
        x = jnp.arange(2 * 4 * 4 * 3, dtype=jnp.float32).reshape(2, 4, 4, 3)
        z = space_to_depth(x, 2)
        assert z.shape == (2, 2, 2, 12)
        # channel index (a, b, c): a = row offset, b = col offset
        np.testing.assert_allclose(z[0, 0, 0, 0:3], x[0, 0, 0])
        np.testing.assert_allclose(z[0, 0, 0, 3:6], x[0, 0, 1])
        np.testing.assert_allclose(z[0, 0, 0, 6:9], x[0, 1, 0])
        np.testing.assert_allclose(z[0, 0, 0, 9:12], x[0, 1, 1])

    def test_indivisible_raises(self):
        with pytest.raises(ValueError, match="divisible"):
            space_to_depth(jnp.zeros((1, 5, 4, 3)), 2)

    def test_stem_equivalence_exact(self, rng):
        """conv(7x7, s2, pad 3) == conv(4x4, s1, pad (2,1)) on the s2d
        input with converted weights — the transform is the same math."""
        from horovod_tpu.models.resnet import convert_stem_weights
        x = jnp.asarray(rng.standard_normal((2, 32, 32, 3)), jnp.float32)
        w7 = jnp.asarray(rng.standard_normal((7, 7, 3, 8)) * 0.1,
                         jnp.float32)

        ref = jax.lax.conv_general_dilated(
            x, w7, window_strides=(2, 2), padding=[(3, 3), (3, 3)],
            dimension_numbers=("NHWC", "HWIO", "NHWC"))

        v4 = jnp.asarray(convert_stem_weights(w7))
        got = jax.lax.conv_general_dilated(
            space_to_depth(x, 2), v4, window_strides=(1, 1),
            padding=[(2, 1), (2, 1)],
            dimension_numbers=("NHWC", "HWIO", "NHWC"))

        assert got.shape == ref.shape == (2, 16, 16, 8)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_resnet_s2d_bf16_stats_trains(self, rng):
        """The full experiment config (stem='s2d', bf16 BN stats) runs
        forward + backward with the right shapes."""
        from horovod_tpu.models.resnet import ResNet, BasicBlock
        model = ResNet(stage_sizes=[1, 1], block_cls=BasicBlock,
                       num_classes=10, num_filters=8, dtype=jnp.float32,
                       bn_stats_dtype=jnp.bfloat16, stem="s2d")
        x = jnp.asarray(rng.standard_normal((2, 32, 32, 3)), jnp.float32)
        variables = model.init(jax.random.PRNGKey(0), x, train=True)
        assert variables["params"]["conv_init"]["kernel"].shape == \
            (4, 4, 12, 8)

        def loss(p):
            logits, _ = model.apply(
                {"params": p,
                 "batch_stats": variables["batch_stats"]},
                x, train=True, mutable=["batch_stats"])
            return jnp.mean(logits ** 2)

        l, g = jax.value_and_grad(loss)(variables["params"])
        assert np.isfinite(float(l))
        assert all(np.all(np.isfinite(np.asarray(a)))
                   for a in jax.tree_util.tree_leaves(g))
