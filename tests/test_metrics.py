"""Metrics & telemetry subsystem (ISSUE 1 tentpole): registry, exporters,
flusher, timeline cross-links, and the collective stall watchdog."""

import json
import re
import threading
import time

import numpy as np
import pytest

import horovod_tpu as hvd
from horovod_tpu import timeline as tl
from horovod_tpu.metrics import (
    LATENCY_BUCKETS, RATIO_BUCKETS, Counter, Gauge, Histogram, StallWatchdog,
    collective_begin, collective_end, collective_summary, pending_collectives,
    registry, reset_metrics, snapshot, start_metrics_flusher,
    stop_metrics_flusher, to_json, to_prometheus,
)


@pytest.fixture(autouse=True)
def _fresh_registry():
    reset_metrics()
    yield
    reset_metrics()


class TestPrimitives:
    def test_counter(self):
        c = Counter()
        c.inc()
        c.inc(41)
        assert c.value == 42
        with pytest.raises(ValueError):
            c.inc(-1)

    def test_gauge(self):
        g = Gauge()
        g.set(2.5)
        assert g.value == 2.5
        g.inc()
        g.dec(0.5)
        assert g.value == 3.0

    def test_histogram_buckets(self):
        h = Histogram(buckets=(0.1, 1.0, 10.0))
        for v in (0.05, 0.5, 5.0, 50.0):
            h.observe(v)
        assert h.count == 4
        assert h.sum == pytest.approx(55.55)
        cum = dict(h.cumulative())
        assert cum[0.1] == 1 and cum[1.0] == 2 and cum[10.0] == 3
        assert cum[float("inf")] == 4

    def test_registry_labels_mint_series(self):
        registry.counter("x_total", kind="a").inc()
        registry.counter("x_total", kind="b").inc(2)
        series = {tuple(s["labels"].items()): s["value"]
                  for s in snapshot()["counters"]["x_total"]}
        assert series == {(("kind", "a"),): 1, (("kind", "b"),): 2}

    def test_histogram_bucket_layout_shared_per_name(self):
        registry.histogram("h", buckets=(1.0, 2.0), kind="a")
        h2 = registry.histogram("h", buckets=(5.0, 6.0), kind="b")
        assert h2.buckets == (1.0, 2.0)   # first registration wins


class TestCollectiveInstrumentation:
    def test_allreduce_populates_collective_counters(self):
        """Acceptance: non-empty calls/bytes counters + latency histogram
        after a single-process allreduce."""
        x = np.ones((hvd.size(), 4), np.float32)
        hvd.allreduce(x, op=hvd.Sum)
        snap = hvd.metrics()
        calls = {tuple(s["labels"].items()): s["value"]
                 for s in snap["counters"]["collective_calls_total"]}
        assert calls[(("kind", "allreduce"),)] >= 1
        nbytes = {tuple(s["labels"].items()): s["value"]
                  for s in snap["counters"]["collective_bytes_total"]}
        assert nbytes[(("kind", "allreduce"),)] >= x.nbytes
        hist = [s for s in snap["histograms"]["collective_dispatch_seconds"]
                if s["labels"] == {"kind": "allreduce"}]
        assert hist and hist[0]["count"] >= 1 and hist[0]["sum"] > 0

    def test_multiple_kinds_label_separately(self):
        hvd.allreduce(np.ones((hvd.size(), 2), np.float32))
        hvd.allgather(np.ones((hvd.size(), 2), np.float32))
        kinds = {s["labels"]["kind"] for s in
                 hvd.metrics()["counters"]["collective_calls_total"]}
        assert {"allreduce", "allgather"} <= kinds

    def test_collective_summary_shape(self):
        hvd.allreduce(np.ones((hvd.size(), 2), np.float32))
        summ = collective_summary()
        assert summ["allreduce"]["calls"] >= 1
        assert summ["allreduce"]["bytes"] > 0

    def test_traced_lowerings_counted_per_compilation(self):
        from jax.sharding import PartitionSpec as P
        f = hvd.spmd(lambda x: hvd.allreduce(x, op=hvd.Sum),
                     in_specs=P("hvd"), out_specs=P("hvd"))
        x = np.ones((hvd.size(), 3), np.float32)
        f(x)
        traced = collective_summary()["allreduce"]["traced_lowerings"]
        assert traced >= 1
        f(x)   # cached program: re-execution must not re-count
        assert collective_summary()["allreduce"]["traced_lowerings"] == traced

    def test_fusion_metrics_recorded_on_trace(self):
        """Fusion fill/flush metrics are trace-time: a fresh shape forces a
        recompile, which runs fuse() and records its buckets."""
        shape = (hvd.size(), 17)   # unlikely-cached shape
        hvd.allreduce({"a": np.ones(shape, np.float32),
                       "b": np.ones(shape, np.float32)}, op=hvd.Sum)
        snap = hvd.metrics()
        assert snap["counters"]["fusion_buckets_total"][0]["value"] >= 1
        assert snap["counters"]["fusion_tensors_total"][0]["value"] >= 2
        causes = {s["labels"]["cause"]
                  for s in snap["counters"]["fusion_flush_total"]}
        assert "end_of_group" in causes or "capacity" in causes
        fill = snap["histograms"]["fusion_fill_ratio"][0]
        assert fill["count"] >= 1

    def test_reset_metrics_clears_counters(self):
        hvd.allreduce(np.ones((hvd.size(), 2), np.float32))
        assert hvd.metrics()["counters"]
        hvd.reset_metrics()
        assert hvd.metrics()["counters"] == {}
        assert hvd.metrics()["gauges"] == {}
        assert hvd.metrics()["histograms"] == {}

    def test_hvd_metrics_is_callable_module(self):
        # hvd.metrics doubles as the submodule and the snapshot call.
        assert hvd.metrics.to_prometheus is to_prometheus
        assert isinstance(hvd.metrics(), dict)


class TestThreadSafety:
    def test_concurrent_counter_increments_are_exact(self):
        c = registry.counter("race_total")
        h = registry.histogram("race_seconds")
        n_threads, n_iter = 8, 500

        def work():
            for _ in range(n_iter):
                c.inc()
                h.observe(0.001)

        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert c.value == n_threads * n_iter
        assert h.count == n_threads * n_iter

    def test_concurrent_series_creation(self):
        errs = []

        def work(i):
            try:
                for j in range(200):
                    registry.counter("mint_total", worker=i % 4).inc()
            except Exception as e:   # pragma: no cover
                errs.append(e)

        threads = [threading.Thread(target=work, args=(i,)) for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errs
        total = sum(s["value"] for s in
                    snapshot()["counters"]["mint_total"])
        assert total == 8 * 200


# One metric line: name{labels} value — the exposition grammar subset the
# exporter emits (no timestamps, no exemplars).
_PROM_LABEL_VALUE = r"\"(?:\\.|[^\"\\])*\""   # escaped \" \\ \n allowed
_PROM_LINE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*"
    r"(\{[a-zA-Z_][a-zA-Z0-9_]*=" + _PROM_LABEL_VALUE +
    r"(,[a-zA-Z_][a-zA-Z0-9_]*=" + _PROM_LABEL_VALUE + r")*\})?"
    r" (\+Inf|-Inf|NaN|[-+]?[0-9]+(\.[0-9]+)?([eE][-+]?[0-9]+)?)$")


class TestExporters:
    def _populate(self):
        registry.counter("calls_total", kind="allreduce").inc(3)
        registry.gauge("world_size").set(8)
        hst = registry.histogram("lat_seconds", buckets=(0.001, 0.1, 1.0))
        for v in (0.0005, 0.05, 0.5, 5.0):
            hst.observe(v)

    def test_prometheus_text_format_parses(self):
        """Acceptance: the exporter output passes a format-validity check —
        every line is a `# HELP`/`# TYPE` header or matches the exposition
        grammar, histogram buckets are cumulative and end at +Inf, and
        _count equals the +Inf bucket."""
        self._populate()
        text = to_prometheus()
        assert text.endswith("\n")
        types = {}
        for line in text.strip().splitlines():
            if line.startswith("# HELP "):
                continue
            if line.startswith("# TYPE "):
                _, _, name, kind = line.split()
                types[name] = kind
                continue
            assert _PROM_LINE.match(line), f"invalid exposition line: {line!r}"
        assert types["horovod_tpu_calls_total"] == "counter"
        assert types["horovod_tpu_world_size"] == "gauge"
        assert types["horovod_tpu_lat_seconds"] == "histogram"
        # histogram structure: cumulative buckets, +Inf == _count
        buckets = re.findall(
            r'horovod_tpu_lat_seconds_bucket\{le="([^"]+)"\} (\d+)', text)
        counts = [int(c) for _, c in buckets]
        assert counts == sorted(counts), "buckets must be cumulative"
        assert buckets[-1][0] == "+Inf"
        count = int(re.search(
            r"horovod_tpu_lat_seconds_count (\d+)", text).group(1))
        assert counts[-1] == count == 4

    def test_prometheus_label_escaping(self):
        registry.counter("esc_total", name='we"ird\nlabel\\x').inc()
        text = to_prometheus()
        line = [l for l in text.splitlines() if "esc_total{" in l][0]
        assert _PROM_LINE.match(line)
        assert '\\"' in line and "\\n" in line

    def test_family_headers_once_with_help(self):
        """Satellite: `# HELP`/`# TYPE` exactly once per family — even when
        the same name exists in two metric kinds — and HELP text escapes
        backslash/newline per the exposition format."""
        from horovod_tpu.metrics import set_help
        self._populate()
        # Same family name as counter AND gauge: headers must not repeat,
        # and the second kind's samples are skipped entirely — one name
        # emitting two samples with the same labelset is a duplicate
        # timeseries, which scrapers reject.
        registry.counter("dup_family").inc()
        registry.gauge("dup_family").set(1)
        set_help("calls_total", "weird\nhelp\\text")
        text = to_prometheus()
        lines = text.strip().splitlines()
        for prefix in ("# HELP ", "# TYPE "):
            names = [l.split()[2] for l in lines if l.startswith(prefix)]
            assert len(names) == len(set(names)), (
                f"duplicate {prefix.strip()} headers: {names}")
        samples = [l for l in lines
                   if l.startswith("horovod_tpu_dup_family")]
        assert len(samples) == 1, samples
        help_line = [l for l in lines
                     if l.startswith("# HELP horovod_tpu_calls_total ")][0]
        assert "\\n" in help_line and "\\\\" in help_line
        assert "\n" not in help_line[len("# HELP "):]
        # Every family with samples has a TYPE header before its samples.
        typed = {l.split()[2] for l in lines if l.startswith("# TYPE ")}
        for l in lines:
            if not l.startswith("#"):
                fam = re.match(r"^([a-zA-Z_:][a-zA-Z0-9_:]*)", l).group(1)
                base = re.sub(r"_(bucket|sum|count)$", "", fam)
                assert fam in typed or base in typed, l

    def test_prometheus_roundtrip_parse(self):
        """Satellite acceptance: parse the exposition text back into
        {family: {labels: value}} and recover exactly the snapshot's
        counter/gauge values and histogram sum/count."""
        self._populate()
        registry.counter("esc2_total", path='a\\b"c\nd').inc(5)
        snap = snapshot()
        parsed = {}
        for line in to_prometheus(snap).strip().splitlines():
            if line.startswith("#"):
                continue
            m = re.match(r"^([a-zA-Z_:][a-zA-Z0-9_:]*)"
                         r"(?:\{(.*)\})? (\S+)$", line)
            assert m, f"unparseable line: {line!r}"
            name, labelstr, value = m.groups()
            labels = {}
            for lm in re.finditer(
                    r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:\\.|[^"\\])*)"',
                    labelstr or ""):
                k, v = lm.groups()
                labels[k] = (v.replace("\\n", "\n").replace('\\"', '"')
                             .replace("\\\\", "\\"))
            parsed.setdefault(name, {})[
                tuple(sorted(labels.items()))] = float(value)
        for name, series in snap["counters"].items():
            for s in series:
                key = tuple(sorted(s["labels"].items()))
                assert parsed[f"horovod_tpu_{name}"][key] == s["value"]
        for name, series in snap["gauges"].items():
            for s in series:
                key = tuple(sorted(s["labels"].items()))
                assert parsed[f"horovod_tpu_{name}"][key] == s["value"]
        for name, series in snap["histograms"].items():
            for s in series:
                key = tuple(sorted(s["labels"].items()))
                assert parsed[f"horovod_tpu_{name}_count"][key] == s["count"]
                assert parsed[f"horovod_tpu_{name}_sum"][key] == \
                    pytest.approx(s["sum"])

    def test_json_roundtrip(self):
        self._populate()
        payload = json.loads(to_json())
        assert payload["counters"] == snapshot()["counters"]
        # round-trips: dumps(loads(x)) re-parses to the same object
        assert json.loads(json.dumps(payload)) == payload

    def test_snapshot_after_allreduce_exports_valid_prometheus(self):
        """Acceptance criterion end-to-end: real allreduce -> snapshot ->
        Prometheus exporter -> validity check."""
        hvd.allreduce(np.ones((hvd.size(), 4), np.float32))
        for line in to_prometheus().strip().splitlines():
            if not line.startswith("#"):
                assert _PROM_LINE.match(line), line
        assert "horovod_tpu_collective_calls_total" in to_prometheus()


class TestFlusher:
    def test_json_flusher_writes_valid_snapshots(self, tmp_path):
        registry.counter("flushed_total").inc(7)
        path = tmp_path / "metrics.json"
        start_metrics_flusher(str(path), interval_s=0.05)
        try:
            deadline = time.monotonic() + 5
            while not path.exists() and time.monotonic() < deadline:
                time.sleep(0.02)
        finally:
            stop_metrics_flusher()
        data = json.loads(path.read_text())
        assert data["counters"]["flushed_total"][0]["value"] == 7

    def test_prom_extension_selects_text_format(self, tmp_path):
        registry.counter("flushed_total").inc(1)
        path = tmp_path / "metrics.prom"
        start_metrics_flusher(str(path), interval_s=60)
        stop_metrics_flusher()          # final write on stop
        text = path.read_text()
        assert "# TYPE horovod_tpu_flushed_total counter" in text

    def test_numpy_counter_increment_stays_json_exportable(self):
        registry.counter("np_total").inc(np.int64(5))
        payload = json.loads(to_json())
        assert payload["counters"]["np_total"][0]["value"] == 5

    def test_atexit_drains_final_snapshot(self, tmp_path):
        """Satellite fix: a short-lived process (serving replica, one-
        shot bench) whose lifetime is shorter than the flush interval
        must still land its FINAL snapshot at interpreter exit — the
        flusher registers an atexit drain; nobody calls stop or
        shutdown here."""
        import os
        import subprocess
        import sys
        import textwrap
        path = tmp_path / "exit_metrics.json"
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        code = textwrap.dedent(f"""
            import os, sys
            os.environ["JAX_PLATFORMS"] = "cpu"
            sys.path.insert(0, {repo!r})
            from horovod_tpu import metrics
            metrics.counter("atexit_probe_total").inc(3)
            metrics.start_metrics_flusher({str(path)!r},
                                          interval_s=3600)
            # fall off the end: only atexit can write the snapshot
        """)
        r = subprocess.run([sys.executable, "-c", code], timeout=300,
                           capture_output=True, text=True)
        assert r.returncode == 0, r.stderr[-2000:]
        data = json.loads(path.read_text())
        assert data["counters"]["atexit_probe_total"][0]["value"] == 3


class TestTimelineCrossLink:
    def test_event_marks_active_timeline(self, tmp_path):
        path = str(tmp_path / "tl.json")
        tl.init_timeline(path)
        registry.event("custom_thing", detail=3)
        tl.shutdown_timeline()
        events = json.load(open(path))["traceEvents"]
        marks = [e for e in events if e["name"] == "custom_thing"]
        assert marks and marks[0]["cat"] == "metrics"
        assert marks[0]["args"]["detail"] == 3
        # and the counter side of the event recorded too
        assert snapshot()["counters"]["custom_thing_total"][0]["value"] == 1

    def test_event_without_timeline_only_counts(self):
        registry.event("lonely_thing")
        assert snapshot()["counters"]["lonely_thing_total"][0]["value"] == 1


class TestStallWatchdog:
    def test_fires_on_stalled_collective_and_names_it(self):
        """Acceptance: detection of a pending collective within the
        configured timeout, without deadlocking the suite (pure
        pending-table stall — nothing actually blocks)."""
        fired = []
        wd = StallWatchdog(timeout_s=0.15, on_stall=fired.append,
                           poll_s=0.03)
        tok = collective_begin("allreduce", name="grad/dense0",
                               nbytes=1024, ranks=(0, 3))
        try:
            with wd:
                deadline = time.monotonic() + 5
                while not fired and time.monotonic() < deadline:
                    time.sleep(0.02)
        finally:
            collective_end(tok)
        assert fired, "watchdog did not fire within 5s"
        rep = fired[0]
        assert rep["tensor"] == "grad/dense0"
        assert rep["kind"] == "allreduce"
        assert rep["process_set"] == [0, 3]
        assert rep["waiting_ranks"] == [0, 3]
        assert rep["pending_s"] >= 0.15
        assert rep["bytes"] == 1024
        assert snapshot()["counters"]["stall_events_total"][0]["value"] >= 1

    def test_fires_once_per_stuck_op(self):
        fired = []
        wd = StallWatchdog(timeout_s=0.05, on_stall=fired.append)
        tok = collective_begin("broadcast", name="w")
        try:
            time.sleep(0.1)
            assert len(wd.check_once()) == 1
            assert wd.check_once() == []      # same op never re-fires
        finally:
            collective_end(tok)
        assert len(fired) == 1

    def test_completed_collective_never_fires(self):
        wd = StallWatchdog(timeout_s=0.05)
        tok = collective_begin("allgather")
        collective_end(tok)
        time.sleep(0.1)
        assert wd.check_once() == []
        assert wd.stall_count == 0

    def test_stall_marker_lands_in_timeline(self, tmp_path):
        path = str(tmp_path / "tl.json")
        tl.init_timeline(path)
        wd = StallWatchdog(timeout_s=0.01)
        tok = collective_begin("allreduce", name="stuck")
        try:
            time.sleep(0.05)
            wd.check_once()
        finally:
            collective_end(tok)
            tl.shutdown_timeline()
        events = json.load(open(path))["traceEvents"]
        stalls = [e for e in events if e["name"] == "collective_stall"]
        assert stalls and stalls[0]["args"]["tensor"] == "stuck"

    def test_global_process_set_reports_world_ranks(self):
        wd = StallWatchdog(timeout_s=0.01)
        tok = collective_begin("allreduce")
        try:
            time.sleep(0.05)
            reports = wd.check_once()
        finally:
            collective_end(tok)
        assert reports[0]["process_set"] == "global"
        assert reports[0]["waiting_ranks"] == list(range(hvd.size()))

    def test_pending_table_tracks_real_collectives(self):
        assert pending_collectives() == []     # nothing in flight
        hvd.allreduce(np.ones((hvd.size(), 2), np.float32))
        assert pending_collectives() == []     # begin/end balanced

    def test_start_stall_watchdog_explicit_args_replace_running(self):
        """init() auto-starts a default watchdog; a later explicit
        start_stall_watchdog(timeout_s=..., on_stall=...) must take
        effect, not be silently swallowed."""
        from horovod_tpu.metrics import (get_stall_watchdog,
                                         start_stall_watchdog,
                                         stop_stall_watchdog)
        default = start_stall_watchdog()       # idle call: returns existing
        assert start_stall_watchdog() is default
        cb = lambda r: None                    # noqa: E731
        try:
            wd = start_stall_watchdog(timeout_s=123.0, on_stall=cb)
            assert wd is not default
            assert wd.timeout_s == 123.0 and wd._on_stall is cb
            assert get_stall_watchdog() is wd
        finally:
            stop_stall_watchdog()
            start_stall_watchdog()             # restore the default one

    def test_timeout_defaults_to_stall_check_config(self, monkeypatch):
        from horovod_tpu import config as hconfig
        monkeypatch.setenv("HOROVOD_STALL_CHECK_TIME_SECONDS", "7.5")
        hconfig.refresh()
        try:
            assert StallWatchdog().timeout_s == 7.5
        finally:
            monkeypatch.undo()
            hconfig.refresh()


class TestTimelineSatelliteFixes:
    def test_flush_survives_native_close_error(self, tmp_path, monkeypatch):
        """Satellite: flush must leave a valid JSON file even when the
        native appender was constructed but close() raises."""
        from horovod_tpu import native
        monkeypatch.setattr(native, "native_available", lambda: False)
        path = str(tmp_path / "tl.json")
        t = tl.Timeline(path)

        class BrokenAppender:
            def event(self, *a, **k):
                pass

            def close(self):
                raise RuntimeError("disk gone")

        t._nt = BrokenAppender()
        t.marker("precious", epoch=1)
        with t.activity("span"):
            pass
        t.flush()                       # must not raise, must not drop
        events = json.load(open(path))["traceEvents"]
        assert {e["name"] for e in events} == {"precious", "span"}

    def test_native_event_error_falls_back_to_python(self, tmp_path,
                                                     monkeypatch):
        from horovod_tpu import native
        monkeypatch.setattr(native, "native_available", lambda: False)
        path = str(tmp_path / "tl.json")
        t = tl.Timeline(path)

        class DyingAppender:
            def event(self, *a, **k):
                raise OSError("pipe broke")

            def close(self):             # pragma: no cover
                raise AssertionError("should have been dropped")

        t._nt = DyingAppender()
        t.marker("kept")
        assert t._nt is None            # appender abandoned mid-stream
        t.flush()
        events = json.load(open(path))["traceEvents"]
        assert [e["name"] for e in events] == ["kept"]

    def test_numpy_marker_args_do_not_break_flush(self, tmp_path,
                                                  monkeypatch):
        from horovod_tpu import native
        monkeypatch.setattr(native, "native_available", lambda: False)
        path = str(tmp_path / "tl.json")
        t = tl.Timeline(path)
        t.marker("m", val=np.float32(1.5))   # unserializable without default=
        t.flush()                            # must still leave valid JSON
        events = json.load(open(path))["traceEvents"]
        assert events[0]["name"] == "m"

    def test_numpy_marker_args_do_not_disable_native(self, tmp_path,
                                                     monkeypatch):
        from horovod_tpu import native
        monkeypatch.setattr(native, "native_available", lambda: False)
        t = tl.Timeline(str(tmp_path / "tl.json"))
        seen = []

        class Appender:
            def event(self, *a, **k):
                seen.append(k)

            def close(self):
                raise RuntimeError("force python fallback")

        t._nt = Appender()
        t.marker("m", val=np.float32(1.5))
        assert t._nt is not None             # serialization != appender death
        assert json.loads(seen[0]["args_json"])  # and it was valid JSON

    def test_start_timeline_twice_flushes_first(self, tmp_path):
        """Satellite: re-init must flush the previous Timeline instead of
        leaking it with an invalid/absent file."""
        p1, p2 = str(tmp_path / "a.json"), str(tmp_path / "b.json")
        tl.start_timeline(p1)
        tl.get_timeline().marker("first")
        tl.start_timeline(p2)           # re-init: must finalize p1
        try:
            events = json.load(open(p1))["traceEvents"]
            assert [e["name"] for e in events] == ["first"]
            tl.get_timeline().marker("second")
        finally:
            tl.stop_timeline()
        events2 = json.load(open(p2))["traceEvents"]
        assert [e["name"] for e in events2] == ["second"]


class TestServeLatencyBuckets:
    """ISSUE 15 satellite: sub-ms histogram resolution for the serving
    latency families, and the live scrape endpoint round-tripping
    through the same exposition grammar."""

    def test_sub_ms_buckets_roundtrip_exposition(self):
        from horovod_tpu.metrics import SERVE_LATENCY_BUCKETS
        assert SERVE_LATENCY_BUCKETS[0] == pytest.approx(2.5e-4)
        h = registry.histogram("serve_ttft_seconds", engine="e0",
                               buckets=SERVE_LATENCY_BUCKETS)
        for v in (2e-4, 3e-4, 8e-4, 2e-3, 0.05):
            h.observe(v)
        text = to_prometheus()
        for line in text.strip().splitlines():
            if not line.startswith("# "):
                assert _PROM_LINE.match(line), line
        buckets = dict(re.findall(
            r'horovod_tpu_serve_ttft_seconds_bucket\{[^}]*le="([^"]+)"\}'
            r" (\d+)", text))
        # the 250us boundary is exposed and resolves the two sub-ms obs
        assert buckets["0.00025"] == "1"
        assert buckets["0.0005"] == "2"
        assert buckets["0.001"] == "3"
        assert buckets["+Inf"] == "5"

    def test_metrics_http_endpoint_roundtrip(self):
        import urllib.request
        from horovod_tpu.metrics import SERVE_LATENCY_BUCKETS
        registry.histogram("serve_tpot_seconds", engine="e9",
                           buckets=SERVE_LATENCY_BUCKETS).observe(3e-4)
        registry.counter("scrape_probe_total").inc(2)
        srv = hvd.metrics_http(0)
        try:
            with urllib.request.urlopen(f"{srv.url}/metrics",
                                        timeout=5) as r:
                assert "version=0.0.4" in r.headers["Content-Type"]
                text = r.read().decode("utf-8")
            for line in text.strip().splitlines():
                if not line.startswith("# "):
                    assert _PROM_LINE.match(line), line
            assert "horovod_tpu_scrape_probe_total 2" in text
            assert 'le="0.00025"' in text
            # /trace serves the live request-span buffer (empty when
            # request tracing is off) as a Chrome-trace doc
            with urllib.request.urlopen(f"{srv.url}/trace",
                                        timeout=5) as r:
                doc = json.loads(r.read().decode("utf-8"))
            assert doc["traceEvents"] == []
            # unknown paths 404 instead of crashing the thread
            import urllib.error
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(f"{srv.url}/nope", timeout=5)
        finally:
            srv.stop()
