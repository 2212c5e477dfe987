"""Overlapped gradient synchronization (overlap.py + the ``algorithm=``
axis of ``hvd.allreduce``): numeric parity of the RS+AG lowerings against
the fused psum across ops/dtypes/process sets/scaling, auto selection,
fusion oversize-leaf splitting, the optimizer/grad overlap modes, config
knob plumbing, and a 2-process end-to-end smoke."""

import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu import overlap


ALGS = ("psum", "rs_ag", "chunked_rs_ag")


def _counter(name, **labels):
    return sum(c["value"] for c in hvd.metrics()["counters"].get(name, [])
               if all(c["labels"].get(k) == v for k, v in labels.items()))


def _tol(dtype):
    if dtype == jnp.bfloat16:
        return dict(rtol=2e-2, atol=2e-2)
    if jnp.issubdtype(jnp.dtype(dtype), jnp.integer):
        return dict(rtol=0, atol=0)
    return dict(rtol=2e-6, atol=1e-5)


class TestAlgorithmParity:
    """psum vs rs_ag vs chunked_rs_ag across ops and dtypes (the
    satellite parity matrix). Sum/Average take the real decomposition;
    Min/Max/Adasum pass through to their existing lowerings, so every
    algorithm must return the psum path's value EXACTLY for those."""

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16,
                                       jnp.int32])
    @pytest.mark.parametrize("op", [hvd.Sum, hvd.Average])
    def test_sum_average_matrix(self, rng, dtype, op):
        n = hvd.size()
        if jnp.issubdtype(jnp.dtype(dtype), jnp.integer):
            x = jnp.asarray(rng.integers(-40, 40, (n, 173)), dtype)
        else:
            x = jnp.asarray(rng.standard_normal((n, 173)), dtype)
        base = np.asarray(hvd.allreduce(x, op=op, algorithm="psum"))
        for alg in ("rs_ag", "chunked_rs_ag"):
            got = np.asarray(hvd.allreduce(x, op=op, algorithm=alg,
                                           overlap_chunks=3))
            np.testing.assert_allclose(
                got.astype(np.float64), base.astype(np.float64),
                err_msg=f"{alg} vs psum, op={op} dtype={dtype}",
                **_tol(dtype))

    @pytest.mark.parametrize("op", [hvd.Min, hvd.Max, hvd.Adasum])
    def test_non_decomposable_ops_pass_through(self, rng, op):
        n = hvd.size()
        x = jnp.asarray(rng.standard_normal((n, 64)), jnp.float32)
        base = np.asarray(hvd.allreduce(x, op=op, algorithm="psum"))
        got = np.asarray(hvd.allreduce(x, op=op,
                                       algorithm="chunked_rs_ag"))
        np.testing.assert_array_equal(got, base)

    def test_prescale_postscale(self, rng):
        n = hvd.size()
        x = rng.standard_normal((n, 97)).astype(np.float32)
        want = x.sum(0) * 0.5 * 3.0
        for alg in ALGS:
            got = np.asarray(hvd.allreduce(
                jnp.asarray(x), op=hvd.Sum, prescale_factor=0.5,
                postscale_factor=3.0, algorithm=alg,
                overlap_chunks=2))[0]
            np.testing.assert_allclose(got, want, rtol=2e-6, atol=1e-5)

    def test_subset_process_set(self, rng):
        n = hvd.size()
        members = [1, 3, 5]
        ps = hvd.add_process_set(members)
        try:
            x = rng.standard_normal((n, 130)).astype(np.float32)
            want = x[members].mean(0)
            for alg in ("rs_ag", "chunked_rs_ag"):
                got = np.asarray(hvd.allreduce(
                    jnp.asarray(x), op=hvd.Average, process_set=ps,
                    algorithm=alg, overlap_chunks=2))
                for m in members:
                    np.testing.assert_allclose(got[m], want, rtol=2e-6,
                                               atol=1e-5)
                # non-members get their input back exactly
                np.testing.assert_array_equal(got[0], x[0])
        finally:
            hvd.remove_process_set(ps)

    def test_traced_lowering_matches(self, rng):
        n = hvd.size()
        x = rng.standard_normal((n, 257)).astype(np.float32)

        def step(v, alg):
            return hvd.allreduce(v, op=hvd.Average, algorithm=alg,
                                 overlap_chunks=4)

        outs = {}
        for alg in ALGS:
            fn = hvd.spmd(lambda v: step(v, alg), in_specs=P("hvd"),
                          out_specs=P("hvd"))
            outs[alg] = np.asarray(fn(jnp.asarray(x)))[0]
        np.testing.assert_allclose(outs["rs_ag"], outs["psum"],
                                   rtol=2e-6, atol=1e-5)
        np.testing.assert_allclose(outs["chunked_rs_ag"], outs["psum"],
                                   rtol=2e-6, atol=1e-5)


QALGS = ("rs_ag_int8", "chunked_rs_ag_int8", "rs_ag_fp8",
         "chunked_rs_ag_fp8")


def _qtol(alg, x, k):
    """Absolute error bound vs the exact psum for a quantized wire:
    two quantization points (per-contribution + re-quantized partial),
    each within half a step of the block max-abs."""
    steps = 127 if "int8" in alg else 8
    return 3.0 * k * float(np.abs(np.asarray(x, np.float32)).max()) / steps


class TestQuantizedAlgorithmParity:
    """The acceptance parity matrix: quantized algorithms agree with
    ``psum`` within per-format error bounds across Sum/Average x
    fp32/bf16 x process-set subsets x traced/eager."""

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("op", [hvd.Sum, hvd.Average])
    @pytest.mark.parametrize("alg", QALGS)
    def test_matrix_eager(self, rng, dtype, op, alg):
        n = hvd.size()
        x = jnp.asarray(rng.standard_normal((n, 777)), dtype)
        base = np.asarray(hvd.allreduce(x, op=op, algorithm="psum")
                          ).astype(np.float64)
        got_j = hvd.allreduce(x, op=op, algorithm=alg, overlap_chunks=3)
        assert got_j.dtype == x.dtype       # wire is internal; dtype kept
        got = np.asarray(got_j).astype(np.float64)
        k = n if op == hvd.Sum else 1
        # bf16 inputs carry their own rounding on the exact path too.
        bound = _qtol(alg, x, k) + (0.1 * k if dtype == jnp.bfloat16
                                    else 0.0)
        assert np.abs(got - base).max() < bound, \
            f"{alg} vs psum, op={op} dtype={dtype}"

    @pytest.mark.parametrize("alg", ["chunked_rs_ag_int8",
                                     "chunked_rs_ag_fp8"])
    @pytest.mark.parametrize("op", [hvd.Sum, hvd.Average])
    def test_subset_process_set(self, rng, alg, op):
        n = hvd.size()
        members = [1, 3, 6]
        ps = hvd.add_process_set(members)
        try:
            x = rng.standard_normal((n, 515)).astype(np.float32)
            got = np.asarray(hvd.allreduce(
                jnp.asarray(x), op=op, process_set=ps, algorithm=alg,
                overlap_chunks=2))
            want = (x[members].sum(0) if op == hvd.Sum
                    else x[members].mean(0))
            k = len(members) if op == hvd.Sum else 1
            for m in members:
                assert np.abs(got[m] - want).max() < _qtol(alg, x, k)
            # members agree exactly (same wire bytes dequantized)
            for m in members[1:]:
                np.testing.assert_array_equal(got[m], got[members[0]])
            # non-members get their input back exactly
            np.testing.assert_array_equal(got[0], x[0])
        finally:
            hvd.remove_process_set(ps)

    @pytest.mark.parametrize("alg", QALGS)
    def test_traced_lowering_matches(self, rng, alg):
        n = hvd.size()
        x = rng.standard_normal((n, 1029)).astype(np.float32)
        fn = hvd.spmd(lambda v: hvd.allreduce(v, op=hvd.Average,
                                              algorithm=alg,
                                              overlap_chunks=4),
                      in_specs=P("hvd"), out_specs=P("hvd"))
        got = np.asarray(fn(jnp.asarray(x)))[0]
        assert np.abs(got - x.mean(0)).max() < _qtol(alg, x, 1)

    def test_non_decomposable_ops_pass_through_exact(self, rng):
        n = hvd.size()
        x = jnp.asarray(rng.standard_normal((n, 64)), jnp.float32)
        for op in (hvd.Min, hvd.Max):
            base = np.asarray(hvd.allreduce(x, op=op, algorithm="psum"))
            got = np.asarray(hvd.allreduce(x, op=op,
                                           algorithm="chunked_rs_ag_int8"))
            np.testing.assert_array_equal(got, base)

    def test_integer_leaves_stay_exact(self, rng):
        n = hvd.size()
        xi = jnp.asarray(rng.integers(-50, 50, (n, 37)), jnp.int32)
        got = np.asarray(hvd.allreduce(xi, op=hvd.Sum,
                                       algorithm="rs_ag_int8"))
        np.testing.assert_array_equal(got[0], np.asarray(xi).sum(0))

    def test_mixed_magnitude_leaves_survive(self, rng):
        """BLOCK-aligned leaf packing: a 100.0-magnitude layer fused with
        a 1e-3 layer must not flush the small one (per-leaf blocks)."""
        n = hvd.size()
        big = np.full((n, 4), 100.0, np.float32)
        small = np.full((n, 1000), 1e-3, np.float32)
        out_big, out_small = hvd.allreduce(
            [big, small], op=hvd.Average, algorithm="chunked_rs_ag_int8")
        np.testing.assert_allclose(np.asarray(out_big)[0], 100.0,
                                   rtol=1e-2)
        np.testing.assert_allclose(np.asarray(out_small)[0], 1e-3,
                                   rtol=2e-2)

    def test_prescale_postscale(self, rng):
        n = hvd.size()
        x = rng.standard_normal((n, 300)).astype(np.float32)
        want = x.sum(0) * 0.5 * 3.0
        got = np.asarray(hvd.allreduce(
            jnp.asarray(x), op=hvd.Sum, prescale_factor=0.5,
            postscale_factor=3.0, algorithm="rs_ag_int8"))[0]
        assert np.abs(got - want).max() < 3.0 * _qtol("int8", x, n)


class TestWireBytesMetrics:
    def test_int8_at_least_3x_fewer_bytes_on_4mb_bucket(self, rng):
        """Acceptance: allreduce_wire_bytes_total shows >= 3x fewer bytes
        for the int8 wire vs fp32 on a >= 4MB bucket."""
        hvd.reset_metrics()
        n = hvd.size()
        m = (4 * 1024 * 1024) // 4          # 1M fp32 elements = 4MB
        x = jnp.asarray(rng.standard_normal((n, m)), jnp.float32)
        hvd.allreduce(x, op=hvd.Sum, algorithm="rs_ag")
        hvd.allreduce(x, op=hvd.Sum, algorithm="rs_ag_int8")
        snap = hvd.metrics()
        by_wire = {}
        for c in snap["counters"]["allreduce_wire_bytes_total"]:
            w = c["labels"]["wire"]
            by_wire[w] = by_wire.get(w, 0) + c["value"]
        assert by_wire["fp32"] >= 4 * 1024 * 1024
        assert by_wire["fp32"] >= 3.0 * by_wire["int8"], by_wire
        ratios = {g["labels"]["wire"]: g["value"]
                  for g in snap["gauges"]["allreduce_compression_ratio"]}
        assert ratios["int8"] > 3.0
        assert ratios["fp32"] == pytest.approx(1.0)

    def test_per_leg_bytes_on_4mb_bucket(self, rng):
        """Multi-leg exchanges must account payload+scales per phase: the
        RS and AG legs of a decomposed allreduce each carry the full
        bucket (ring factor aside), so a single lump-sum counter
        undercounts the wire by the leg structure and skews
        allreduce_compression_ratio for 2D/swing lowerings."""
        from horovod_tpu.ops.quantized import BLOCK
        hvd.reset_metrics()
        n = hvd.size()
        # distinct from the sibling test's bucket so the counters see a
        # fresh trace (they count per compiled bucket, not per call);
        # BLOCK-aligned so the fused int8 bucket carries no padding
        m = (4 * 1024 * 1024) // 4 + 16 * BLOCK
        x = jnp.asarray(rng.standard_normal((n, m)), jnp.float32)
        hvd.allreduce(x, op=hvd.Sum, algorithm="rs_ag")
        hvd.allreduce(x, op=hvd.Sum, algorithm="rs_ag_int8")
        snap = hvd.metrics()
        legs = {}
        for c in snap["counters"]["allreduce_wire_bytes_total"]:
            lab = c["labels"]
            legs[(lab["algorithm"], lab.get("phase"))] = c["value"]
        # fp32: each leg is the full bucket payload, counted separately
        assert legs[("rs_ag", "rs")] == 4 * m
        assert legs[("rs_ag", "ag")] == 4 * m
        # int8: each leg is payload + one fp32 scale per started block
        scales = 4 * ((m + BLOCK - 1) // BLOCK)
        assert legs[("rs_ag_int8", "rs")] == m + scales
        assert legs[("rs_ag_int8", "ag")] == m + scales

    def test_int8_dtype_payload_not_labeled_as_quantized_wire(self, rng):
        """An EXACT exchange of an int8-dtype tensor must label as
        raw-int8: wire="int8" always means the quantized format (else
        phantom scale bytes and a false doctor finding)."""
        hvd.reset_metrics()
        n = hvd.size()
        x = jnp.asarray(rng.integers(-100, 100, (n, 512)), jnp.int8)
        got = np.asarray(hvd.allreduce(x, op=hvd.Sum, algorithm="psum"))
        np.testing.assert_array_equal(
            got[0], np.asarray(x).astype(np.int64).sum(0).astype(np.int8))
        snap = hvd.metrics()
        wires = {c["labels"]["wire"]: c["value"]
                 for c in snap["counters"]["allreduce_wire_bytes_total"]}
        assert "int8" not in wires
        # per-device bucket: 512 elems x 1 B, no phantom scale bytes
        assert wires["raw-int8"] == 512

    def test_env_algorithm_auto_enables_error_feedback(self, monkeypatch):
        """HOROVOD_ALLREDUCE_ALGORITHM=chunked_rs_ag_int8 with no
        algorithm kwarg must still wrap the optimizer in error feedback
        (review finding: the env spelling trained uncompensated)."""
        import optax
        from horovod_tpu import config as hconfig
        monkeypatch.setenv("HOROVOD_ALLREDUCE_ALGORITHM",
                           "chunked_rs_ag_int8")
        hconfig.refresh()
        try:
            opt = hvd.DistributedOptimizer(optax.sgd(0.1))
            state = opt.init({"w": jnp.ones(4)})
            assert isinstance(state, hvd.ErrorFeedbackState)
        finally:
            monkeypatch.delenv("HOROVOD_ALLREDUCE_ALGORITHM")
            hconfig.refresh()
        # and the exact default stays unwrapped
        opt = hvd.DistributedOptimizer(optax.sgd(0.1))
        assert not isinstance(opt.init({"w": jnp.ones(4)}),
                              hvd.ErrorFeedbackState)

    def test_bf16_wire_halves_bytes(self, rng):
        hvd.reset_metrics()
        n = hvd.size()
        x = jnp.asarray(rng.standard_normal((n, 4096)), jnp.float32)
        base = np.asarray(hvd.allreduce(x, op=hvd.Average,
                                        algorithm="rs_ag"))
        got = np.asarray(hvd.allreduce(x, op=hvd.Average,
                                       algorithm="rs_ag", wire="bf16"))
        assert got.dtype == np.float32      # cast back after the wire
        np.testing.assert_allclose(got[0], base[0], rtol=2e-2, atol=2e-2)
        snap = hvd.metrics()
        by_wire = {}
        for c in snap["counters"]["allreduce_wire_bytes_total"]:
            by_wire[c["labels"]["wire"]] = \
                by_wire.get(c["labels"]["wire"], 0) + c["value"]
        assert by_wire["fp32"] == 2 * by_wire["bf16"]


class TestAutoSelection:
    def test_size_cutoffs(self):
        r = overlap.resolve_algorithm
        # the exact wire: XLA's own all-reduce at every size (PR 32)
        for nbytes in (1024, overlap.RS_AG_MIN_BYTES,
                       overlap.CHUNKED_MIN_BYTES):
            assert r("auto", nbytes, hvd.Sum, 8, True) == "psum"
        # the cutoffs choose where the wire is quantized inside RS+AG
        assert r("auto", overlap.RS_AG_MIN_BYTES - 1, hvd.Sum, 8, True,
                 wire="int8") == "psum"
        assert r("auto", overlap.RS_AG_MIN_BYTES, hvd.Sum, 8, True,
                 wire="int8") == "rs_ag_int8"
        assert r("auto", overlap.CHUNKED_MIN_BYTES, hvd.Sum, 8, True,
                 wire="int8") == "chunked_rs_ag_int8"

    def test_non_reducible_and_tiny_world(self):
        r = overlap.resolve_algorithm
        # Min/Max/Adasum (reducible=False) always pass through
        assert r("chunked_rs_ag", 1 << 30, hvd.Min, 8, False) == "psum"
        # a single device has nothing to scatter
        assert r("rs_ag", 1 << 30, hvd.Sum, 1, True) == "psum"
        # quantized requests pass through identically
        assert r("chunked_rs_ag_int8", 1 << 30, hvd.Min, 8,
                 False) == "psum"

    def test_wire_upgrades_auto_picks(self):
        r = overlap.resolve_algorithm
        # the wire default upgrades auto's rs_ag picks, leaves psum exact
        assert r("auto", 1024, hvd.Sum, 8, True, wire="int8") == "psum"
        assert r("auto", overlap.RS_AG_MIN_BYTES, hvd.Sum, 8, True,
                 wire="int8") == "rs_ag_int8"
        assert r("auto", overlap.CHUNKED_MIN_BYTES, hvd.Sum, 8, True,
                 wire="fp8") == "chunked_rs_ag_fp8"
        # bf16 wire is a cast around the collective, not a restructured
        # reduction: exact-wire rule, and an explicit name stays as it is
        assert r("auto", overlap.RS_AG_MIN_BYTES, hvd.Sum, 8, True,
                 wire="bf16") == "psum"
        assert r("rs_ag", overlap.RS_AG_MIN_BYTES, hvd.Sum, 8, True,
                 wire="bf16") == "rs_ag"
        # explicit algorithm wins over the wire default
        assert r("psum", overlap.CHUNKED_MIN_BYTES, hvd.Sum, 8, True,
                 wire="int8") == "psum"

    def test_parse_compose_roundtrip(self):
        assert overlap.parse_algorithm("chunked_rs_ag_int8") == \
            ("chunked_rs_ag", "int8")
        assert overlap.parse_algorithm("rs_ag_fp8") == ("rs_ag", "fp8")
        assert overlap.parse_algorithm("rs_ag") == ("rs_ag", None)
        assert overlap.compose_algorithm("rs_ag", "int8") == "rs_ag_int8"
        assert overlap.compose_algorithm("rs_ag", "bf16") == "rs_ag"
        assert overlap.compose_algorithm("psum", "int8") == "psum"
        for alg in overlap.ALGORITHMS:
            base, w = overlap.parse_algorithm(alg)
            assert overlap.compose_algorithm(base, w) == alg

    def test_wire_bytes_accounting(self):
        from horovod_tpu.ops.quantized import BLOCK
        n = 4 * BLOCK
        assert overlap.wire_bytes(n, "fp32") == 4 * n
        assert overlap.wire_bytes(n, "bf16") == 2 * n
        assert overlap.wire_bytes(n, "int8") == n + 16
        assert overlap.wire_bytes(n, "fp8") == n + 16
        # ragged tail: one extra started block's scale
        assert overlap.wire_bytes(n + 1, "int8") == n + 1 + 20

    def test_unknown_wire_rejected(self):
        with pytest.raises(ValueError, match="wire"):
            hvd.allreduce(jnp.zeros((hvd.size(), 2)), wire="int4")

    def test_unknown_algorithm_raises(self):
        with pytest.raises(ValueError, match="butterfly"):
            overlap._reject_algorithm("butterfly")
        with pytest.raises(ValueError, match="algorithm"):
            hvd.allreduce(jnp.zeros((hvd.size(), 2)), algorithm="butterfly")

    def test_rejection_names_composed_form_and_knob(self):
        # A known base composed with a wire that has no quantized
        # lowering must name the composed form it actually received and
        # the knob that set it — not just dump ALGORITHMS.
        with pytest.raises(ValueError) as ei:
            hvd.allreduce(jnp.zeros((hvd.size(), 2)),
                          algorithm="psum_int8")
        msg = str(ei.value)
        assert "psum_int8" in msg and "allreduce(algorithm=...)" in msg
        assert "exact by construction" in msg

    def test_bad_chunks_raises(self):
        with pytest.raises(ValueError, match="overlap_chunks"):
            hvd.allreduce(jnp.zeros((hvd.size(), 2)), overlap_chunks=0)


class TestChunkedPrimitive:
    def test_split_sizes(self):
        # 100 elements over 8 devices in 3 chunks: per-chunk multiple of
        # 8, no all-padding chunks, covers the buffer
        per, chunks = overlap._split_sizes(100, 8, 3)
        assert per % 8 == 0 and per * chunks >= 100 and chunks == 3
        # degenerate: tiny buffer clamps the chunk count
        per, chunks = overlap._split_sizes(5, 8, 4)
        assert chunks == 1 and per == 8
        assert overlap._split_sizes(0, 8, 4)[1] == 1

    def test_ragged_sizes_pad_and_unpad(self, rng):
        n = hvd.size()
        # deliberately not divisible by world size or chunk count
        for m in (1, 7, 1001):
            x = rng.standard_normal((n, m)).astype(np.float32)
            got = np.asarray(hvd.allreduce(
                jnp.asarray(x), op=hvd.Sum, algorithm="chunked_rs_ag",
                overlap_chunks=3))
            assert got.shape == (n, m)
            np.testing.assert_allclose(got[0], x.sum(0), rtol=2e-6,
                                       atol=1e-5)


class TestFusionOversizeSplit:
    def test_split_roundtrip_and_cap(self, rng):
        from horovod_tpu import fusion
        leaves = [jnp.asarray(rng.standard_normal(100), jnp.float32),
                  jnp.asarray(rng.standard_normal(10000), jnp.float32)]
        buckets, unpack = fusion.fuse(leaves, threshold_bytes=1024)
        # every bucket respects the threshold — the oversize leaf split
        # into tile-aligned sub-chunks instead of one giant bucket
        assert all(int(b.size) * 4 <= 1024 for b in buckets)
        assert len(buckets) > 2
        out = unpack(buckets)
        for a, b in zip(leaves, out):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_unpack_is_static_slices(self, rng):
        """unpack must lower to static lax.slice, not dynamic-slice."""
        from horovod_tpu import fusion
        leaves = [jnp.zeros(100, jnp.float32), jnp.zeros(60, jnp.float32)]

        def f():
            buckets, unpack = fusion.fuse(leaves, threshold_bytes=1 << 20)
            return unpack(buckets)

        text = jax.make_jaxpr(f)().pretty_print()
        assert "dynamic_slice" not in text

    def test_allreduce_through_split_buckets(self, rng):
        n = hvd.size()
        x = rng.standard_normal((n, 5000)).astype(np.float32)
        got = np.asarray(hvd.allreduce(
            jnp.asarray(x), op=hvd.Sum, fusion_threshold_bytes=4096,
            algorithm="chunked_rs_ag", overlap_chunks=2))
        np.testing.assert_allclose(got[0], x.sum(0), rtol=2e-6,
                                   atol=1e-5)


class TestOverlapModes:
    def _problem(self, rng):
        n = hvd.size()
        W = {"l1": {"w": jnp.asarray(rng.standard_normal((4, 8)),
                                     jnp.float32)},
             "l2": {"w": jnp.asarray(rng.standard_normal(8),
                                     jnp.float32)}}
        X = jnp.asarray(rng.standard_normal((n, 4)), jnp.float32)

        def loss(w, x):
            return jnp.sum((x @ w["l1"]["w"] * w["l2"]["w"]) ** 2)
        return W, X, loss

    def test_grad_overlap_taps_match_plain(self, rng):
        W, X, loss = self._problem(rng)

        def step(w, x):
            g0 = hvd.grad(loss)(w, x)
            g1 = hvd.grad(loss, overlap=True,
                          algorithm="chunked_rs_ag",
                          overlap_chunks=2)(w, x)
            return g0, g1

        f = hvd.spmd(step, in_specs=(P(), P("hvd")), out_specs=(P(), P()))
        g0, g1 = f(W, X)
        for a, b in zip(jax.tree_util.tree_leaves(g0),
                        jax.tree_util.tree_leaves(g1)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)

    def test_optimizer_overlap_matches_plain(self, rng):
        import optax
        W, X, loss = self._problem(rng)
        opt0 = hvd.DistributedOptimizer(optax.sgd(0.1))
        opt1 = hvd.DistributedOptimizer(optax.sgd(0.1), overlap=True,
                                        algorithm="rs_ag")

        def step(w, x):
            g = jax.grad(loss)(w, x)
            u0, _ = opt0.update(g, opt0.init(w), w)
            u1, _ = opt1.update(g, opt1.init(w), w)
            return u0, u1

        f = hvd.spmd(step, in_specs=(P(), P("hvd")), out_specs=(P(), P()))
        u0, u1 = f(W, X)
        for a, b in zip(jax.tree_util.tree_leaves(u0),
                        jax.tree_util.tree_leaves(u1)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)

    def test_tap_outside_spmd_is_identity(self, rng):
        x = {"a": jnp.asarray(rng.standard_normal(4), jnp.float32)}
        g = jax.grad(lambda p: jnp.sum(overlap.tap_params(p)["a"] ** 2))(x)
        np.testing.assert_allclose(np.asarray(g["a"]),
                                   2 * np.asarray(x["a"]), rtol=1e-6)


class TestConfigKnobs:
    # What the lowered README step holds for each schedule: the way to
    # time one is the variable around ``benchmark/run.py`` (PERFORMANCE.md),
    # so the variable has to reach the step's collectives.
    @pytest.mark.parametrize("alg, has, has_not", [
        ("psum", ["all_reduce"],
         ["reduce_scatter", "collective_permute"]),
        ("rs_ag", ["reduce_scatter", "all_gather"], ["collective_permute"]),
        ("chunked_rs_ag", ["reduce_scatter", "all_gather"],
         ["collective_permute"]),
        ("swing", ["collective_permute"], ["reduce_scatter"]),
    ], ids=["psum", "rs_ag", "chunked_rs_ag", "swing"])
    def test_env_plumbing_and_gauges(self, monkeypatch, rng, alg, has,
                                     has_not):
        import optax
        from horovod_tpu import config as hconfig
        monkeypatch.setenv("HOROVOD_ALLREDUCE_ALGORITHM", alg)
        monkeypatch.setenv("HOROVOD_OVERLAP_CHUNKS", "7")
        cfg = hconfig.refresh()
        try:
            assert cfg.allreduce_algorithm == alg
            assert cfg.overlap_chunks == 7
            assert hvd.build_info()["allreduce_algorithm"] == alg
            W, X, loss = TestOverlapModes()._problem(rng)
            opt = hvd.DistributedOptimizer(optax.sgd(0.1))

            def train_step(w, opt_state, x):
                value, grads = hvd.value_and_grad(loss)(w, x)
                updates, opt_state = opt.update(grads, opt_state, w)
                return optax.apply_updates(w, updates), opt_state, value

            step = hvd.spmd(train_step, in_specs=(P(), P(), P("hvd")),
                            out_specs=(P(), P(), P()))
            lowered_as = _counter("allreduce_algorithm_total", algorithm=alg)
            text = step.lower(W, opt.init(W), X).as_text()
            buckets = _counter("allreduce_algorithm_total",
                               algorithm=alg) - lowered_as
            assert buckets >= 1
            for op in has:
                assert f"stablehlo.{op}" in text, (alg, op)
            for op in has_not:
                assert f"stablehlo.{op}" not in text, (alg, op)
            # a pair a bucket, or a pair for each chunk of each bucket
            pairs = text.count("stablehlo.reduce_scatter")
            if alg == "rs_ag":
                assert pairs == buckets
            elif alg == "chunked_rs_ag":
                assert pairs > buckets
        finally:
            monkeypatch.delenv("HOROVOD_ALLREDUCE_ALGORITHM")
            monkeypatch.delenv("HOROVOD_OVERLAP_CHUNKS")
            hconfig.refresh()

    def test_invalid_algorithm_env_raises(self, monkeypatch):
        from horovod_tpu import config as hconfig
        monkeypatch.setenv("HOROVOD_ALLREDUCE_ALGORITHM", "ring2d")
        with pytest.raises(ValueError, match="ring2d"):
            hconfig.refresh()
        monkeypatch.delenv("HOROVOD_ALLREDUCE_ALGORITHM")
        hconfig.refresh()

    def test_wire_env_plumbing(self, monkeypatch):
        from horovod_tpu import config as hconfig
        monkeypatch.setenv("HOROVOD_ALLREDUCE_WIRE", "int8")
        cfg = hconfig.refresh()
        try:
            assert cfg.allreduce_wire == "int8"
            assert hvd.build_info()["allreduce_wire"] == "int8"
        finally:
            monkeypatch.delenv("HOROVOD_ALLREDUCE_WIRE")
            hconfig.refresh()
        assert hconfig.refresh().allreduce_wire == "fp32"

    def test_invalid_wire_env_raises(self, monkeypatch):
        from horovod_tpu import config as hconfig
        monkeypatch.setenv("HOROVOD_ALLREDUCE_WIRE", "int4")
        with pytest.raises(ValueError, match="int4"):
            hconfig.refresh()
        monkeypatch.delenv("HOROVOD_ALLREDUCE_WIRE")
        hconfig.refresh()

    def test_wire_gauge_visible(self):
        snap = hvd.metrics()
        if "config_allreduce_wire" not in snap.get("gauges", {}):
            hvd.init()
            snap = hvd.metrics()
        wires = {g["labels"]["wire"]: g["value"]
                 for g in snap["gauges"]["config_allreduce_wire"]}
        assert sum(wires.values()) == 1     # one-hot on the resolved wire

    def test_invalid_chunks_env_raises(self, monkeypatch):
        from horovod_tpu import config as hconfig
        for bad in ("0", "-2", "four"):
            monkeypatch.setenv("HOROVOD_OVERLAP_CHUNKS", bad)
            with pytest.raises(ValueError, match="HOROVOD_OVERLAP_CHUNKS"):
                hconfig.refresh()
        monkeypatch.delenv("HOROVOD_OVERLAP_CHUNKS")
        hconfig.refresh()

    def test_init_never_edits_compiler_flags(self, monkeypatch):
        # The HOROVOD_XLA_LATENCY_HIDING knob appended --xla_tpu_* flags
        # to XLA_FLAGS, which jaxlib 0.9 answers by aborting the process.
        # The knob is gone: init() leaves both flag variables alone even
        # when an old environment still sets it.
        monkeypatch.setenv("HOROVOD_XLA_LATENCY_HIDING", "1")
        before = {k: os.environ.get(k)
                  for k in ("XLA_FLAGS", "LIBTPU_INIT_ARGS")}
        hvd.init()
        assert {k: os.environ.get(k) for k in before} == before
        assert "latency_hiding" not in (os.environ.get("XLA_FLAGS") or "")
        assert not hasattr(overlap, "enable_latency_hiding")
        assert "xla_latency_hiding" not in hvd.build_info()

    def test_config_gauges_visible(self):
        snap = hvd.metrics()
        if "config_overlap_chunks" not in snap.get("gauges", {}):
            # an earlier test's reset_metrics() wiped the init-time
            # stamp; re-init re-resolves the knobs and re-stamps.
            hvd.init()
            snap = hvd.metrics()
        gauges = snap.get("gauges", {})
        assert "config_overlap_chunks" in gauges
        assert "config_allreduce_algorithm" in gauges


class TestAlgorithmMetrics:
    def test_per_bucket_counter_and_chunk_bytes(self, rng):
        hvd.reset_metrics()
        n = hvd.size()
        x = jnp.asarray(rng.standard_normal((n, 640)), jnp.float32)
        hvd.allreduce(x, op=hvd.Sum, algorithm="chunked_rs_ag",
                      overlap_chunks=4, name="metrics_probe")
        snap = hvd.metrics()
        counts = {tuple(sorted(c["labels"].items())): c["value"]
                  for c in snap["counters"]["allreduce_algorithm_total"]}
        assert counts.get((("algorithm", "chunked_rs_ag"),), 0) >= 1
        assert "allreduce_chunk_bytes" in snap.get("histograms", {})


class TestOverlapReport:
    def _shard(self, rank, intervals):
        events = [{"name": "EXEC", "ph": "X", "ts": a, "dur": b - a,
                   "args": {"op_id": i + 1}}
                  for i, (a, b) in enumerate(intervals)]
        return {"rank": rank, "events": events}

    def test_serialized_is_zero_overlapped_is_positive(self):
        from horovod_tpu.trace_merge import overlap_report
        serial = self._shard(0, [(0, 10), (10, 20), (20, 30)])
        piped = self._shard(1, [(0, 10), (5, 15), (10, 20)])
        rep = overlap_report([serial, piped])
        assert rep["by_rank"]["0"]["overlap_efficiency"] == 0.0
        assert rep["by_rank"]["1"]["overlap_efficiency"] > 0.3
        assert 0.0 < rep["overlap_efficiency"] < 1.0

    def test_traced_and_empty_spans_ignored(self):
        from horovod_tpu.trace_merge import overlap_report
        shard = {"rank": 0, "events": [
            {"name": "EXEC", "ts": 0, "dur": 5, "args": {"op_id": -3}},
            {"name": "QUEUE", "ts": 0, "dur": 5, "args": {"op_id": 1}},
        ]}
        rep = overlap_report([shard])
        assert rep["by_rank"]["0"]["exec_spans"] == 0
        assert rep["overlap_efficiency"] == 0.0


class TestTwoProcessSmoke:
    def test_overlap_smoke_two_process(self):
        """Acceptance drive: 2 real processes, same train loop under
        psum and chunked RS+AG, identical parameters on every rank
        (tools/overlap_smoke.py, also `make overlap-smoke`)."""
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        r = subprocess.run(
            [sys.executable,
             os.path.join(repo, "tools", "overlap_smoke.py")],
            capture_output=True, text=True, timeout=500)
        assert r.returncode == 0, \
            f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
        assert "overlap-smoke OK" in r.stdout
