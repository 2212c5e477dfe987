"""hvd.doctor() automated diagnosis: golden-report over a canned
metrics+trace fixture, the offline CLI, and the 2-process doctor smoke."""

import json
import os
import subprocess
import sys

import pytest

import horovod_tpu as hvd
from horovod_tpu import profiler
from horovod_tpu.profiler import doctor, format_report, registry

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean():
    registry.reset()
    hvd.reset_metrics()
    yield
    registry.reset()
    hvd.reset_metrics()


def _ctr(name, value, **labels):
    return {"labels": labels, "value": value}


# ---------------------------------------------------------------------------
# canned fixture: a run with a manufactured straggler AND a recompile
# (plus background noise: healthy fusion, no stalls) — the golden input
# the satellite task asks for.
# ---------------------------------------------------------------------------

def _fixture_snapshot():
    return {
        "counters": {
            "recompiles_total": [
                _ctr("recompiles_total", 3, program="train_step"),
            ],
            "recompile_blame_total": [
                _ctr("recompile_blame_total", 3, program="train_step",
                     argument="seq_len"),
            ],
            "collective_calls_total": [
                _ctr("collective_calls_total", 40, kind="allreduce"),
            ],
        },
        "gauges": {},
        "histograms": {
            # healthy fill: must NOT produce a fusion finding
            "fusion_fill_ratio": [
                {"labels": {}, "count": 10, "sum": 8.0, "buckets": []},
            ],
        },
        "pending_collectives": [],
    }


def _fixture_trace_report():
    # rank 1 charged 250ms of peer wait across 3 correlated collectives
    return {
        "collectives": [{"op_id": i} for i in range(3)],
        "blame_seconds_by_rank": {"0": 0.004, "1": 0.25},
        "critical_path_seconds": 0.31,
    }


def _fixture_programs():
    return {
        "train_step": {
            "name": "train_step", "kind": "step",
            "recompiles": 3, "expected_recompiles": False,
            "last_blame": ["seq_len"],
            "blame_detail": {"seq_len": ["128", "256"]},
        },
    }


class TestGoldenReport:
    def test_ranked_findings_over_canned_fixture(self):
        """Satellite acceptance: doctor over a canned metrics+trace
        fixture with a manufactured straggler and recompile ranks both,
        and the recompile finding names the blamed argument."""
        report = doctor(snapshot=_fixture_snapshot(),
                        trace=_fixture_trace_report(),
                        programs=_fixture_programs())
        findings = report["findings"]
        assert findings, "golden fixture produced no findings"
        # ranked: severities non-increasing, rank field sequential
        sev = [f["severity"] for f in findings]
        assert sev == sorted(sev, reverse=True)
        assert [f["rank"] for f in findings] == list(
            range(1, len(findings) + 1))
        cats = [f["category"] for f in findings]
        assert "straggler" in cats and "recompile" in cats
        # healthy subsystems stay silent
        assert "fusion_fill" not in cats and "stall" not in cats
        assert report["healthy"] is False

    def test_straggler_finding_blames_rank_1(self):
        report = doctor(snapshot=_fixture_snapshot(),
                        trace=_fixture_trace_report(), programs={})
        s = [f for f in report["findings"]
             if f["category"] == "straggler"][0]
        assert s["evidence"]["blamed_rank"] == 1
        assert s["evidence"]["blame_seconds"] == pytest.approx(0.25)
        assert "rank 1" in s["title"]

    def test_recompile_finding_names_blamed_argument(self):
        report = doctor(snapshot=_fixture_snapshot(), trace=None,
                        programs=_fixture_programs())
        r = [f for f in report["findings"]
             if f["category"] == "recompile"][0]
        assert r["evidence"]["program"] == "train_step"
        assert r["evidence"]["recompiles"] == 3
        assert "seq_len" in r["evidence"]["blamed_arguments"]
        assert "seq_len" in r["title"]
        # the old -> new signature detail surfaces in the report text
        assert "128" in r["detail"] and "256" in r["detail"]

    def test_expected_recompiles_not_flagged(self):
        progs = _fixture_programs()
        progs["train_step"]["expected_recompiles"] = True
        report = doctor(snapshot=_fixture_snapshot(), trace=None,
                        programs=progs)
        assert not [f for f in report["findings"]
                    if f["category"] == "recompile"]

    def test_expected_recompiles_skip_survives_offline_snapshot(self):
        # An OFFLINE doctor (perf_doctor over flusher files, no live
        # registry) must still skip by-design churn: the expected tag
        # rides expected_recompiles_total in the exported snapshot.
        snap = _fixture_snapshot()
        snap["counters"]["recompiles_total"].append(
            _ctr("recompiles_total", 4, program="autotuned_step"))
        snap["counters"]["expected_recompiles_total"] = [
            _ctr("expected_recompiles_total", 4, program="autotuned_step")]
        report = doctor(snapshot=snap, trace=None, programs={})
        flagged = [f["evidence"]["program"] for f in report["findings"]
                   if f["category"] == "recompile"]
        assert "train_step" in flagged            # real churn still flagged
        assert "autotuned_step" not in flagged    # by-design churn skipped

    def test_autotuned_note_trace_exports_expected_counter(self):
        # The live end of the same contract: expected=True note_trace
        # recompiles bump expected_recompiles_total in the registry.
        from horovod_tpu import metrics as _metrics
        profiler.note_trace("at_prog", {"threshold": "1"}, expected=True)
        profiler.note_trace("at_prog", {"threshold": "2"}, expected=True)
        snap = _metrics.snapshot()
        vals = {s["labels"].get("program"): s["value"]
                for s in snap["counters"].get(
                    "expected_recompiles_total", [])}
        assert vals.get("at_prog") == 1
        report = doctor(snapshot=snap, trace=None, programs={})
        assert not [f for f in report["findings"]
                    if f["category"] == "recompile"]

    def test_blame_falls_back_to_metrics_labels(self):
        # No registry record (e.g. another rank's snapshot): the blamed
        # argument still comes from recompile_blame_total labels.
        report = doctor(snapshot=_fixture_snapshot(), trace=None,
                        programs={})
        r = [f for f in report["findings"]
             if f["category"] == "recompile"][0]
        assert "seq_len" in r["evidence"]["blamed_arguments"]

    def test_healthy_run_is_healthy(self):
        report = doctor(snapshot={"counters": {}, "gauges": {},
                                  "histograms": {}},
                        trace=None, programs={})
        assert report["healthy"] is True
        assert report["findings"] == []
        assert "nothing looks sick" in format_report(report)

    def test_low_mfu_finding(self):
        progs = {"bench:gpt2": {
            "name": "bench:gpt2", "expected_mfu": 0.5,
            "last_step_seconds": 0.1,
            "utilization": {"mfu": 0.1, "hfu": 0.3},
        }}
        report = doctor(snapshot={"counters": {}, "gauges": {},
                                  "histograms": {}},
                        trace=None, programs=progs)
        m = [f for f in report["findings"] if f["category"] == "low_mfu"]
        assert m and m[0]["evidence"]["program"] == "bench:gpt2"

    def test_total_rejection_is_backpressure_finding(self):
        # An engine rejecting EVERYTHING has submitted == 0 — the worst
        # backpressure case must not read healthy.
        snap = {
            "counters": {
                "serve_requests_total": [
                    _ctr("serve_requests_total", 50, status="rejected"),
                ],
            },
            "gauges": {}, "histograms": {},
        }
        report = doctor(snapshot=snap, trace=None, programs={})
        bp = [f for f in report["findings"]
              if f["category"] == "serving_backpressure"]
        assert bp and bp[0]["evidence"]["rejected"] == 50

    def test_serving_slo_and_memory_findings(self):
        snap = {
            "counters": {
                "serve_requests_total": [
                    _ctr("serve_requests_total", 100, status="submitted"),
                    _ctr("serve_requests_total", 30, status="expired"),
                ],
                "memory_pressure_total": [_ctr("memory_pressure_total", 2)],
            },
            "gauges": {}, "histograms": {},
        }
        report = doctor(snapshot=snap, trace=None, programs={})
        cats = [f["category"] for f in report["findings"]]
        assert "serving_slo" in cats and "memory_pressure" in cats

    def test_low_mfu_from_offline_snapshot_gauges(self):
        # Offline perf_doctor runs with an empty registry; the mfu check
        # must still work from the exported program_mfu /
        # program_expected_mfu gauges.
        snap = {
            "counters": {}, "histograms": {},
            "gauges": {
                "program_mfu": [
                    {"labels": {"program": "bench:gpt2"}, "value": 0.1}],
                "program_hfu": [
                    {"labels": {"program": "bench:gpt2"}, "value": 0.3}],
                "program_expected_mfu": [
                    {"labels": {"program": "bench:gpt2"}, "value": 0.5}],
            },
        }
        report = doctor(snapshot=snap, trace=None, programs={})
        m = [f for f in report["findings"] if f["category"] == "low_mfu"]
        assert m and m[0]["evidence"]["program"] == "bench:gpt2"

    def test_low_overlap_from_offline_trace_report(self):
        # merge_timelines(feed_metrics=False) never feeds the gauge; the
        # overlap section of the report must carry the finding offline —
        # but only with enough EXEC spans to mean anything.
        trace = dict(_fixture_trace_report())
        trace["overlap"] = {
            "by_rank": {"0": {"exec_spans": 8, "overlap_efficiency": 0.0},
                        "1": {"exec_spans": 8, "overlap_efficiency": 0.0}},
            "overlap_efficiency": 0.0,
        }
        empty = {"counters": {}, "gauges": {}, "histograms": {}}
        report = doctor(snapshot=empty, trace=trace, programs={})
        assert [f for f in report["findings"]
                if f["category"] == "low_overlap"]
        # a 3-collective smoke (too few spans) is not an overlap signal
        trace["overlap"]["by_rank"] = {
            "0": {"exec_spans": 3, "overlap_efficiency": 0.0}}
        report = doctor(snapshot=empty, trace=trace, programs={})
        assert not [f for f in report["findings"]
                    if f["category"] == "low_overlap"]

    def test_uncompressed_wire_suggests_quantization(self):
        snap = {
            "counters": {"allreduce_wire_bytes_total": [
                _ctr("allreduce_wire_bytes_total", 48 * 1024 * 1024,
                     algorithm="chunked_rs_ag", wire="fp32"),
            ]},
            "gauges": {}, "histograms": {}, "pending_collectives": [],
        }
        rep = doctor(snapshot=snap, trace=None, programs={})
        wire = [f for f in rep["findings"]
                if f["category"] == "wire_uncompressed"]
        assert len(wire) == 1
        assert "HOROVOD_ALLREDUCE_WIRE=int8" in wire[0]["suggestion"]
        assert "error_feedback" in wire[0]["suggestion"]

    def test_quantized_wire_reports_achieved_compression(self):
        snap = {
            "counters": {"allreduce_wire_bytes_total": [
                _ctr("allreduce_wire_bytes_total", 13 * 1024 * 1024,
                     algorithm="chunked_rs_ag_int8", wire="int8"),
                _ctr("allreduce_wire_bytes_total", 1 * 1024 * 1024,
                     algorithm="psum", wire="fp32"),
            ]},
            "gauges": {"allreduce_compression_ratio": [
                {"labels": {"wire": "int8"}, "value": 3.94},
            ]},
            "histograms": {}, "pending_collectives": [],
        }
        rep = doctor(snapshot=snap, trace=None, programs={})
        wire = [f for f in rep["findings"]
                if f["category"] == "wire_compression"]
        assert len(wire) == 1
        assert "3.9x" in wire[0]["title"]
        assert rep["healthy"]           # informational, not a defect
        # no double finding: the uncompressed suggestion must not fire
        assert not [f for f in rep["findings"]
                    if f["category"] == "wire_uncompressed"]

    def test_small_uncompressed_traffic_is_quiet(self):
        snap = {
            "counters": {"allreduce_wire_bytes_total": [
                _ctr("allreduce_wire_bytes_total", 1024,
                     algorithm="psum", wire="fp32"),
            ]},
            "gauges": {}, "histograms": {}, "pending_collectives": [],
        }
        rep = doctor(snapshot=snap, trace=None, programs={})
        assert not [f for f in rep["findings"]
                    if f["category"].startswith("wire")]

    def test_format_report_renders_every_finding(self):
        report = doctor(snapshot=_fixture_snapshot(),
                        trace=_fixture_trace_report(),
                        programs=_fixture_programs())
        text = format_report(report)
        for f in report["findings"]:
            assert f["title"] in text
            assert f["suggestion"] in text

    def test_report_is_json_serializable(self):
        report = doctor(snapshot=_fixture_snapshot(),
                        trace=_fixture_trace_report(),
                        programs=_fixture_programs())
        assert json.loads(json.dumps(report)) is not None

    def test_trace_accepts_merged_doc_and_path(self, tmp_path):
        merged = {"traceEvents": [],
                  "stragglerReport": _fixture_trace_report()}
        r1 = doctor(snapshot={"counters": {}, "gauges": {},
                              "histograms": {}},
                    trace=merged, programs={})
        path = tmp_path / "merged.json"
        path.write_text(json.dumps(merged))
        r2 = doctor(snapshot={"counters": {}, "gauges": {},
                              "histograms": {}},
                    trace=str(path), programs={})
        assert [f["category"] for f in r1["findings"]] == \
            [f["category"] for f in r2["findings"]] != []


def _gau(value, **labels):
    return {"labels": labels, "value": value}


class TestShardingCheck:
    """_check_sharding: replicated params + memory-bound symptoms →
    suggest HOROVOD_MESH (ISSUE 14 satellite)."""

    def _snap(self, **gauges):
        base = {"counters": {}, "gauges": {}, "histograms": {},
                "pending_collectives": []}
        base["gauges"].update(gauges)
        return base

    def test_peak_hbm_near_limit_suggests_mesh(self):
        snap = self._snap(
            config_mesh_dp=[_gau(8.0)], config_mesh_mp=[_gau(1.0)],
            device_hbm_bytes_limit=[_gau(100.0, device="0")],
            program_peak_hbm_bytes=[_gau(90.0, program="train_step")])
        report = doctor(snapshot=snap, trace=None, programs={})
        fs = [f for f in report["findings"]
              if f["category"] == "sharding"]
        assert fs and "train_step" in fs[0]["title"]
        assert "HOROVOD_MESH=dp4xmp2" in fs[0]["suggestion"]
        assert fs[0]["evidence"]["peak_hbm_bytes"] == 90.0

    def test_quiet_when_already_model_sharded(self):
        snap = self._snap(
            config_mesh_dp=[_gau(4.0)], config_mesh_mp=[_gau(2.0)],
            device_hbm_bytes_limit=[_gau(100.0, device="0")],
            program_peak_hbm_bytes=[_gau(99.0, program="train_step")])
        report = doctor(snapshot=snap, trace=None, programs={})
        assert not [f for f in report["findings"]
                    if f["category"] == "sharding"]

    def test_quiet_when_headroom(self):
        snap = self._snap(
            config_mesh_dp=[_gau(8.0)], config_mesh_mp=[_gau(1.0)],
            device_hbm_bytes_limit=[_gau(100.0, device="0")],
            program_peak_hbm_bytes=[_gau(50.0, program="train_step")])
        report = doctor(snapshot=snap, trace=None, programs={})
        assert not [f for f in report["findings"]
                    if f["category"] == "sharding"]

    def test_kv_quant_rejections_suggest_mesh(self):
        snap = self._snap(
            config_mesh_dp=[_gau(2.0)], config_mesh_mp=[_gau(1.0)],
            serve_kv_quant_enabled=[_gau(1.0, engine="e0")],
            serve_kv_pool_bytes_capacity=[_gau(4096.0, engine="e0")])
        snap["counters"]["serve_requests_total"] = [
            {"labels": {"engine": "e0", "status": "rejected"},
             "value": 3}]
        report = doctor(snapshot=snap, trace=None, programs={})
        fs = [f for f in report["findings"]
              if f["category"] == "sharding"]
        assert fs and fs[0]["evidence"]["rejected"] == 3
        assert "HOROVOD_MESH=dp1xmp2" in fs[0]["suggestion"]

    def test_no_kv_finding_without_quant(self):
        snap = self._snap(
            config_mesh_dp=[_gau(2.0)], config_mesh_mp=[_gau(1.0)],
            serve_kv_quant_enabled=[_gau(0.0, engine="e0")],
            serve_kv_pool_bytes_capacity=[_gau(4096.0, engine="e0")])
        snap["counters"]["serve_requests_total"] = [
            {"labels": {"engine": "e0", "status": "rejected"},
             "value": 3}]
        report = doctor(snapshot=snap, trace=None, programs={})
        assert not [f for f in report["findings"]
                    if f["category"] == "sharding"]

    def test_healthy_is_quiet(self):
        report = doctor(snapshot=self._snap(), trace=None, programs={})
        assert not [f for f in report["findings"]
                    if f["category"] == "sharding"]


class TestPerfDoctorCLI:
    def _import_tool(self):
        sys.path.insert(0, os.path.join(_REPO, "tools"))
        try:
            import perf_doctor
        finally:
            sys.path.remove(os.path.join(_REPO, "tools"))
        return perf_doctor

    def test_merge_snapshots_concatenates_series(self, tmp_path):
        perf_doctor = self._import_tool()
        a = tmp_path / "a.json"
        b = tmp_path / "b.json"
        a.write_text(json.dumps({
            "counters": {"x_total": [_ctr("x_total", 1, rank="0")]},
            "pending_collectives": [{"tensor": "t"}]}))
        b.write_text(json.dumps({
            "counters": {"x_total": [_ctr("x_total", 2, rank="1")]}}))
        merged = perf_doctor._merge_snapshots([str(a), str(b)])
        assert len(merged["counters"]["x_total"]) == 2
        assert merged["pending_collectives"] == [{"tensor": "t"}]

    def test_cli_exit_codes(self, tmp_path):
        sick = tmp_path / "sick.json"
        sick.write_text(json.dumps(_fixture_snapshot()))
        healthy = tmp_path / "ok.json"
        healthy.write_text(json.dumps(
            {"counters": {}, "gauges": {}, "histograms": {}}))
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        tool = os.path.join(_REPO, "tools", "perf_doctor.py")
        r = subprocess.run(
            [sys.executable, tool, "--metrics", str(sick), "--json"],
            capture_output=True, text=True, timeout=240, env=env)
        assert r.returncode == 2, r.stdout + r.stderr
        doc = json.loads(r.stdout)
        assert [f for f in doc["findings"] if f["category"] == "recompile"]
        r = subprocess.run(
            [sys.executable, tool, "--metrics", str(healthy)],
            capture_output=True, text=True, timeout=240, env=env)
        assert r.returncode == 0, r.stdout + r.stderr


# ---------------------------------------------------------------------------
# two-process doctor smoke (make doctor-smoke)
# ---------------------------------------------------------------------------

class TestTwoProcessSmoke:
    def test_doctor_smoke_two_process(self, tmp_path):
        """Acceptance drive: 2 real processes, a manufactured 750ms
        straggler and a forced recompile; hvd.doctor() must rank both and
        name the blamed argument (tools/doctor_smoke.py, also
        `make doctor-smoke`)."""
        r = subprocess.run(
            [sys.executable,
             os.path.join(_REPO, "tools", "doctor_smoke.py")],
            capture_output=True, text=True, timeout=500)
        assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
        assert "doctor-smoke OK" in r.stdout


# ---------------------------------------------------------------------------
# request-tail triage from the request-trace report (PR 15)
# ---------------------------------------------------------------------------

def _rreport(dominant, mean, *, blame=None, hedged=0, worst=None):
    return {
        "count": 4, "hedged": hedged,
        "ttft_p50_s": sum(mean.values()), "ttft_p99_s": sum(mean.values()),
        "breakdown_mean_s": mean, "dominant_component": dominant,
        "replica_blame_s": blame or {}, "dominant_replica": worst,
        "requests": [],
    }


_EMPTY_SNAP = {"counters": {}, "gauges": {}, "histograms": {}}


class TestRequestTailFindings:
    def test_queue_dominated_names_slots_knob(self):
        rep = doctor(snapshot=_EMPTY_SNAP, programs={}, trace={
            "requestReport": _rreport("queue", {
                "queue": 0.08, "prefill": 0.01, "decode": 0.005,
                "push": 0.0, "hedge_wait": 0.0, "other": 0.005})})
        tail = [f for f in rep["findings"]
                if f["category"] == "request_tail"]
        assert tail and tail[0]["evidence"]["dominant"] == "queue"
        assert "HOROVOD_SERVE_SLOTS" in tail[0]["suggestion"]
        assert tail[0]["evidence"]["fraction"] == pytest.approx(0.8)

    def test_hedge_wait_dominated_blames_replica(self):
        rep = doctor(snapshot=_EMPTY_SNAP, programs={}, trace={
            "requestReport": _rreport(
                "hedge_wait",
                {"queue": 0.005, "prefill": 0.01, "decode": 0.005,
                 "push": 0.0, "hedge_wait": 0.09, "other": 0.0},
                blame={"r0": 0.36, "r1": 0.02}, hedged=3, worst="r0")})
        tail = [f for f in rep["findings"]
                if f["category"] == "request_tail"]
        assert tail and tail[0]["evidence"]["slow_replica"] == "r0"
        assert "r0" in tail[0]["title"]
        assert tail[0]["evidence"]["hedged"] == 3

    def test_prefill_dominated_stays_quiet(self):
        # prefill/decode dominance is the model doing work — the triage
        # only fires for queue / push / hedge_wait (actionable waits).
        rep = doctor(snapshot=_EMPTY_SNAP, programs={}, trace={
            "requestReport": _rreport("prefill", {
                "queue": 0.001, "prefill": 0.2, "decode": 0.05,
                "push": 0.001, "hedge_wait": 0.0, "other": 0.002})})
        assert not [f for f in rep["findings"]
                    if f["category"] == "request_tail"]

    def test_slo_burn_cites_traced_breakdown(self):
        snap = {
            "counters": {
                "serve_requests_total": [
                    _ctr("serve_requests_total", 100, status="submitted"),
                    _ctr("serve_requests_total", 30, status="expired"),
                ],
            },
            "gauges": {}, "histograms": {},
        }
        rep = doctor(snapshot=snap, programs={}, trace={
            "requestReport": _rreport("queue", {
                "queue": 0.08, "prefill": 0.01, "decode": 0.005,
                "push": 0.0, "hedge_wait": 0.0, "other": 0.005})})
        slo = [f for f in rep["findings"] if f["category"] == "serving_slo"]
        assert slo and "queue 80.0ms" in slo[0]["detail"]
