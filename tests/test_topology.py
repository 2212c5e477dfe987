"""Topology-aware collectives (ISSUE: torus-native multi-phase RS+AG and
the Swing schedule): torus detection/override plumbing, topology-aware
``auto`` resolution and degradation, per-phase wire-byte accounting, the
acceptance parity matrix for ``rs_ag_2d``/``chunked_rs_ag_2d``/``swing``
vs ``psum`` on a simulated 2x4 torus, doctor's topology finding, the
trace-merge algorithm summary, and the 4-process 2x2 smoke."""

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
from horovod_tpu.parallel import mesh as hmesh


TALGS = ("rs_ag_2d", "chunked_rs_ag_2d", "swing")


class _FakeDev:
    """Stand-in for a TPU device: .coords + .core_on_chip."""

    def __init__(self, coords, core=0):
        self.coords = coords
        self.core_on_chip = core


class TestTopologyDetection:
    def test_parse_topology_grammar(self):
        assert hmesh.parse_topology("2x2") == (2, 2)
        assert hmesh.parse_topology("4X8") == (4, 8)
        assert hmesh.parse_topology("16") == (16,)
        for bad in ("", "2xbanana", "0x4", "-2x4", "x", "2x"):
            with pytest.raises(ValueError, match="HOROVOD_TOPOLOGY"):
                hmesh.parse_topology(bad)

    def test_override_validates_product(self):
        assert hmesh.detect_topology(8, override="2x4") == (2, 4)
        with pytest.raises(ValueError, match="8"):
            hmesh.detect_topology(8, override="3x3")

    def test_cpu_falls_back_to_ring(self):
        # CPU devices have no .coords: the world is a 1-D ring.
        assert hmesh.detect_topology(len(jax.devices()), jax.devices()) \
            == (len(jax.devices()),)
        assert hmesh.detect_topology(1) == (1,)

    def test_tpu_coords_spans(self):
        # 2x2 chip grid, single core per chip: extent-1 dims dropped.
        devs = [_FakeDev((x, y, 0)) for x in range(2) for y in range(2)]
        assert hmesh.detect_topology(4, devs) == (2, 2)
        # 2 chips x 2 cores: core_on_chip becomes the trailing dim.
        devs = [_FakeDev((x, 0, 0), core=c) for x in range(2)
                for c in range(2)]
        assert hmesh.detect_topology(4, devs) == (2, 2)
        # span product that cannot explain the world -> ring fallback
        devs = [_FakeDev((x, 0, 0)) for x in range(2)] * 3
        assert hmesh.detect_topology(6, devs) == (6,)

    def test_make_mesh_does_not_hide_a_failed_placement_on_tpu(
            self, monkeypatch):
        # On a TPU, create_device_mesh aligns logical axes with the torus;
        # its failure used to be swallowed into a naive reshape that runs
        # with tp/sp collectives laid across the fabric at random.
        def refuse(*a, **kw):
            raise NotImplementedError("no placement for this slice")

        monkeypatch.setattr(hmesh.mesh_utils, "create_device_mesh", refuse)
        assert hmesh.make_mesh({"dp": 4, "tp": 2}).shape == \
            {"dp": 4, "tp": 2}                  # CPU: plain reshape
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        with pytest.raises(NotImplementedError, match="no placement"):
            hmesh.make_mesh({"dp": 4, "tp": 2})

    def test_torus_groups(self):
        g = hmesh.torus_groups((2, 4))
        # dim 0: columns of the row-major 2x4 grid; dim 1: the rows
        assert g[0] == [[0, 4], [1, 5], [2, 6], [3, 7]]
        assert g[1] == [[0, 1, 2, 3], [4, 5, 6, 7]]
        # every dim's groups partition the world
        for groups in g:
            flat = sorted(r for grp in groups for r in grp)
            assert flat == list(range(8))


class TestResolveTopologyAware:
    def r(self, *a, **kw):
        return overlap.resolve_algorithm(*a, **kw)

    @pytest.mark.parametrize("world", [1, 4, 8])
    @pytest.mark.parametrize("wire", ["fp32", "int8"])
    @pytest.mark.parametrize("nbytes", [1 << 10, 4 << 20, 32 << 20, 64 << 20],
                             ids=["1KB", "4MB", "32MB", "64MB"])
    @pytest.mark.parametrize("dims", [(2, 2), (2, 4), (4, 4), (8,), None],
                             ids=["2x2", "2x4", "4x4", "ring8", "none"])
    def test_auto_resolve_matrix(self, dims, nbytes, wire, world):
        """What ``auto`` resolves a Sum bucket to. On the exact wire it is
        ``psum`` at every size on every fabric (PR 32: on the one fabric
        timed, a v5e 2x2, every decomposition lost to XLA's own
        all-reduce, and nothing is shipped for a fabric nobody timed). A
        quantized wire needs the decomposition to quantize inside, so
        there the size cutoffs and the torus still choose."""
        got = self.r("auto", nbytes, hvd.Sum, world, True, wire=wire,
                     topology=dims)
        if world == 1 or wire == "fp32" or nbytes < overlap.RS_AG_MIN_BYTES:
            assert got == "psum"
            return
        base = ("chunked_rs_ag" if nbytes >= overlap.CHUNKED_MIN_BYTES
                else "rs_ag")
        if dims is not None and sum(d > 1 for d in dims) >= 2:
            base += "_2d"
        assert got == f"{base}_{wire}"

    def test_explicit_2d_degrades_to_1d_base(self):
        # a pinned *_2d on a 1-D ring runs the 1-D base, same wire
        assert self.r("rs_ag_2d", 1 << 20, hvd.Sum, 8, True,
                      topology=(8,)) == "rs_ag"
        assert self.r("chunked_rs_ag_2d_int8", 1 << 20, hvd.Sum, 8,
                      True, topology=None) == "chunked_rs_ag_int8"
        # with a real torus the explicit request sticks
        assert self.r("rs_ag_2d", 1 << 20, hvd.Sum, 8, True,
                      topology=(2, 4)) == "rs_ag_2d"

    def test_swing_needs_power_of_two_world(self):
        assert self.r("swing", 1 << 20, hvd.Sum, 6, True) == "psum"
        assert self.r("swing", 1 << 20, hvd.Sum, 8, True) == "swing"

    def test_unknown_rejected(self):
        with pytest.raises(ValueError, match="butterfly"):
            self.r("butterfly", 1024, hvd.Sum, 8, True)


class TestWireBytesByPhase:
    def test_psum_single_leg(self):
        assert overlap.wire_bytes_by_phase("psum", 1000, "fp32", 8) \
            == {"all": 4000}

    def test_rs_ag_two_legs(self):
        got = overlap.wire_bytes_by_phase("rs_ag", 1000, "fp32", 8)
        assert got == {"rs": 4000, "ag": 4000}

    def test_2d_phases_shrink_by_dim_extent(self):
        got = overlap.wire_bytes_by_phase("rs_ag_2d", 1000, "fp32", 8,
                                          dims=(2, 4))
        # RS d0 sees the full bucket; RS d1 the 1/2 shard; AG mirrors.
        assert got == {"rs_d0": 4000, "rs_d1": 2000,
                       "ag_d1": 2000, "ag_d0": 4000}
        # degraded (no usable torus): one RS + one AG over the full ring
        got = overlap.wire_bytes_by_phase("rs_ag_2d", 1000, "fp32", 8,
                                          dims=None)
        assert got == {"rs_d0": 4000, "ag_d0": 4000}

    def test_swing_geometric_series(self):
        got = overlap.wire_bytes_by_phase("swing", 1024, "fp32", 8)
        # sum over steps of m/2^(s+1) = c*(n-1) elements per direction
        assert got == {"rs": 4 * 128 * 7, "ag": 4 * 128 * 7}

    def test_quantized_scales_ride_every_leg(self):
        from horovod_tpu.ops.quantized import BLOCK
        m = 8 * BLOCK
        got = overlap.wire_bytes_by_phase("rs_ag_2d", m, "int8", 8,
                                          dims=(2, 4))
        for ph, b in got.items():
            assert b > 0 and b < 4 * m      # compressed on every leg
        assert got["rs_d0"] == m + 4 * (m // BLOCK)


@pytest.fixture(scope="class")
def torus_2x4():
    """Re-init the 8-device world as a simulated 2x4 torus."""
    os.environ["HOROVOD_TOPOLOGY"] = "2x4"
    try:
        hvd.init()
        assert hvd.topology() == (2, 4)
        yield
    finally:
        del os.environ["HOROVOD_TOPOLOGY"]
        hvd.init()


def _qtol(alg, x, k):
    steps = 127 if "int8" in alg else 8
    return 3.0 * k * float(np.abs(np.asarray(x, np.float32)).max()) / steps


@pytest.mark.usefixtures("torus_2x4")
class TestTopologyParityMatrix:
    """Acceptance matrix: the topology-aware schedules agree with
    ``psum`` across Sum/Average x fp32/bf16 x subset process sets x
    eager/traced x wire=fp32/int8 on the simulated 2x4 torus, and every
    row (rank) of the eager result is bit-identical."""

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("op", [hvd.Sum, hvd.Average])
    @pytest.mark.parametrize("alg", TALGS)
    def test_matrix_eager(self, rng, dtype, op, alg):
        n = hvd.size()
        x = jnp.asarray(rng.standard_normal((n, 1001)), dtype)
        base = np.asarray(hvd.allreduce(x, op=op, algorithm="psum")
                          ).astype(np.float64)
        got_j = hvd.allreduce(x, op=op, algorithm=alg, overlap_chunks=3)
        assert got_j.dtype == x.dtype
        got = np.asarray(got_j)
        # cross-rank agreement: every row holds the same bytes
        for r in range(1, n):
            np.testing.assert_array_equal(got[r], got[0])
        got = got.astype(np.float64)
        if dtype == jnp.bfloat16:
            # within ~1 bf16 ulp of the psum result (different but
            # equally-valid reduction orders at 8-bit mantissa)
            bound = float(np.abs(base).max()) * 2.0 ** -7 + 1e-6
        else:
            bound = 1e-5 + 2e-6 * float(np.abs(base).max())
        assert np.abs(got - base).max() <= bound, \
            f"{alg} vs psum, op={op} dtype={dtype}"

    @pytest.mark.parametrize("alg", ["rs_ag_2d_int8",
                                     "chunked_rs_ag_2d_int8",
                                     "rs_ag_2d_fp8"])
    def test_matrix_quantized_wire(self, rng, alg):
        n = hvd.size()
        x = jnp.asarray(rng.standard_normal((n, 901)), jnp.float32)
        base = np.asarray(hvd.allreduce(x, op=hvd.Average,
                                        algorithm="psum"))
        got = np.asarray(hvd.allreduce(x, op=hvd.Average, algorithm=alg,
                                       overlap_chunks=2))
        for r in range(1, n):
            np.testing.assert_array_equal(got[r], got[0])
        assert np.abs(got - base).max() < _qtol(alg, x, 1)

    @pytest.mark.parametrize("op", [hvd.Sum, hvd.Average])
    @pytest.mark.parametrize("alg", TALGS + ("chunked_rs_ag_2d_int8",))
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
            tol = (_qtol(alg, x, k) if "int8" in alg
                   else 1e-4 * max(1.0, k))
            for m in members:
                assert np.abs(got[m] - want).max() < tol, (alg, op)
            for m in members[1:]:
                np.testing.assert_array_equal(got[m], got[members[0]])
            # non-members get their input back exactly
            np.testing.assert_array_equal(got[0], x[0])
        finally:
            hvd.remove_process_set(ps)

    @pytest.mark.parametrize("alg", TALGS)
    def test_traced_lowering_matches(self, rng, alg):
        n = hvd.size()
        x = rng.standard_normal((n, 1029)).astype(np.float32)
        fn = hvd.spmd(lambda v: hvd.allreduce(v, op=hvd.Average,
                                              algorithm=alg,
                                              overlap_chunks=3),
                      in_specs=P("hvd"), out_specs=P("hvd"))
        ref = hvd.spmd(lambda v: hvd.allreduce(v, op=hvd.Average,
                                               algorithm="psum"),
                       in_specs=P("hvd"), out_specs=P("hvd"))
        got = np.asarray(fn(jnp.asarray(x)))
        base = np.asarray(ref(jnp.asarray(x)))
        np.testing.assert_allclose(got, base, rtol=2e-6, atol=1e-5)

    def test_auto_on_the_detected_torus(self):
        # what core.topology() detected, fed through: the exact wire
        # stays on the whole-axis all-reduce at every size, a quantized
        # wire still takes the torus's phases
        topo = hvd.topology()
        assert topo == (2, 4)
        for nbytes in (4 * 1024 * 1024, 32 * 1024 * 1024):
            assert overlap.resolve_algorithm(
                "auto", nbytes, hvd.Sum, hvd.size(), True,
                topology=topo) == "psum"
        assert overlap.resolve_algorithm(
            "auto", 32 * 1024 * 1024, hvd.Sum, hvd.size(), True,
            wire="int8", topology=topo) == "chunked_rs_ag_2d_int8"

    def test_metrics_observability(self, rng):
        """allreduce_algorithm_total{algorithm="rs_ag_2d"} plus all four
        per-phase wire-byte legs show up in hvd.metrics()."""
        hvd.reset_metrics()
        n = hvd.size()
        x = jnp.asarray(rng.standard_normal((n, 2003)), jnp.float32)
        hvd.allreduce(x, op=hvd.Sum, algorithm="rs_ag_2d",
                      name="topo_metrics_probe")
        snap = hvd.metrics()
        algs = {c["labels"]["algorithm"]: c["value"]
                for c in snap["counters"]["allreduce_algorithm_total"]}
        assert algs.get("rs_ag_2d", 0) >= 1, algs
        legs = {c["labels"]["phase"]: c["value"]
                for c in snap["counters"]["allreduce_wire_bytes_total"]
                if c["labels"]["algorithm"] == "rs_ag_2d"}
        assert set(legs) == {"rs_d0", "rs_d1", "ag_d1", "ag_d0"}
        assert legs["rs_d0"] == 4 * 2003            # full bucket, dim 0
        assert legs["rs_d1"] == 4 * -(-2003 // 2)   # 1/2 shard, dim 1
        assert legs["ag_d0"] == legs["rs_d0"]

    def test_build_info_and_gauges(self):
        assert hvd.build_info()["topology"] == "2x4"
        assert hvd.topology() == (2, 4)
        snap = hvd.metrics()
        if "config_topology" not in snap.get("gauges", {}):
            hvd.init()      # an earlier reset_metrics wiped the stamp
            snap = hvd.metrics()
        dims = {g["labels"]["dim"]: g["value"]
                for g in snap["gauges"]["config_topology"]}
        assert dims["0"] == 2 and dims["1"] == 4
        # unused trailing slots are zeroed, not absent (offline parity)
        assert dims["2"] == 0 and dims["3"] == 0


class TestTopologyConfig:
    def test_invalid_spec_rejected_at_refresh(self, monkeypatch):
        from horovod_tpu import config as hconfig
        monkeypatch.setenv("HOROVOD_TOPOLOGY", "2xbanana")
        with pytest.raises(ValueError, match="HOROVOD_TOPOLOGY"):
            hconfig.refresh()
        monkeypatch.delenv("HOROVOD_TOPOLOGY")
        hconfig.refresh()

    def test_product_mismatch_rejected_at_init(self, monkeypatch):
        from horovod_tpu import config as hconfig
        monkeypatch.setenv("HOROVOD_TOPOLOGY", "3x3")
        try:
            with pytest.raises(ValueError, match="3x3"):
                hvd.init()
        finally:
            monkeypatch.delenv("HOROVOD_TOPOLOGY")
            hconfig.refresh()
            hvd.init()

    def test_build_info_before_init_shows_override(self, monkeypatch):
        from horovod_tpu import config as hconfig
        monkeypatch.setenv("HOROVOD_TOPOLOGY", "2x4")
        hconfig.refresh()
        try:
            assert hconfig.get_config().topology == "2x4"
        finally:
            monkeypatch.delenv("HOROVOD_TOPOLOGY")
            hconfig.refresh()


def _ctr(value, **labels):
    return {"labels": labels, "value": value}


def _topo_gauges(*dims):
    vals = list(dims) + [0] * (4 - len(dims))
    return [{"labels": {"dim": str(i)}, "value": v}
            for i, v in enumerate(vals)]


class TestDoctorTopology:
    def _snap(self, gauges, counters):
        return {"counters": counters, "gauges": gauges,
                "histograms": {}, "pending_collectives": []}

    def _findings(self, torus, counters):
        from horovod_tpu.profiler import doctor
        snap = self._snap(
            {"config_topology": _topo_gauges(*torus)},
            {"allreduce_wire_bytes_total": counters})
        rep = doctor(snapshot=snap, trace=None, programs={})
        return [x for x in rep["findings"]
                if x["category"] == "topology_ring"]

    @pytest.mark.parametrize("alg,phases", [
        ("chunked_rs_ag_2d", ("rs_d0", "ag_d0")),
        ("rs_ag_2d", ("rs_d0", "ag_d0")),
        ("chunked_rs_ag", ("rs", "ag")),
        ("rs_ag", ("rs", "ag"))])
    def test_decomposition_on_the_timed_2x2_suggests_psum(self, alg, phases):
        # what the chip refuted (PR 32): the advice is now the opposite
        f = self._findings((2, 2), [
            _ctr(24 * 1024 * 1024, algorithm=alg, wire="fp32", phase=ph)
            for ph in phases])
        assert len(f) == 1
        assert "psum" in f[0]["suggestion"]
        assert "rs_ag_2d" not in f[0]["suggestion"]
        assert "2x2" in f[0]["title"] and alg in f[0]["title"]

    def test_quiet_when_psum_rides(self):
        assert not self._findings((2, 2), [
            _ctr(480 * 1024 * 1024, algorithm="psum", wire="fp32")])

    def test_quiet_on_a_fabric_nobody_timed(self):
        # nothing is advised, either way, where nothing was measured
        for torus in ((2, 4), (8,), (4, 4)):
            for alg in ("chunked_rs_ag", "chunked_rs_ag_2d"):
                assert not self._findings(torus, [
                    _ctr(48 * 1024 * 1024, algorithm=alg, wire="fp32",
                         phase="rs")])

    def test_quiet_for_a_quantized_wire(self):
        # the quantized wire needs the decomposition to quantize inside
        assert not self._findings((2, 2), [
            _ctr(48 * 1024 * 1024, algorithm="chunked_rs_ag_2d_int8",
                 wire="int8", phase="rs_d0")])

    def test_quiet_below_threshold(self):
        assert not self._findings((2, 2), [
            _ctr(1024, algorithm="rs_ag_2d", wire="fp32", phase="rs_d0")])


class TestTraceMergeAlgorithms:
    def test_marker_summary(self):
        from horovod_tpu.trace_merge import overlap_report
        mk = {"name": "allreduce_algorithm", "ph": "i", "ts": 1.0,
              "args": {"algorithm": "rs_ag_2d", "wire": "fp32",
                       "wire_bytes": 120, "topology": "2x4",
                       "phases": {"rs_d0": 40, "rs_d1": 20,
                                  "ag_d1": 20, "ag_d0": 40}}}
        shards = [
            {"rank": 0, "events": [mk, dict(mk)]},
            # higher ranks carry the same trace-time markers; the summary
            # must read one representative shard, not multiply them
            {"rank": 1, "events": [mk]},
        ]
        rep = overlap_report(shards)
        alg = rep["algorithms"]["rs_ag_2d"]
        assert alg["buckets"] == 2
        assert alg["wire_bytes"] == 240
        assert alg["phase_bytes"] == {"rs_d0": 80, "rs_d1": 40,
                                      "ag_d1": 40, "ag_d0": 80}
        assert alg["topology"] == "2x4"
        assert alg["wire"] == "fp32"


class TestFourProcessTopoSmoke:
    def test_topo_smoke_four_process(self):
        """Acceptance drive: 4 real processes on a simulated 2x2 torus,
        bit-identical results across ranks for every topology-aware
        schedule (tools/topo_smoke.py, also `make topo-smoke`)."""
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        r = subprocess.run(
            [sys.executable, os.path.join(repo, "tools", "topo_smoke.py")],
            capture_output=True, text=True, timeout=600)
        assert r.returncode == 0, \
            f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
        assert "topo-smoke OK" in r.stdout
