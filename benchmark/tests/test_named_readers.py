"""The readers over what the program names itself (``readers/named.py``), on
synthetic events that use operation names as the v5e printed them, on a
synthetic registry snapshot and on the program's real one.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_named_readers.py -q

Not part of the repo's tier-1 tests: the benchmark checks itself.
"""

import json
import os
import sys
from types import SimpleNamespace

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import pytest  # noqa: E402

from readers import named  # noqa: E402
from reduce import xplane  # noqa: E402

# operation names exactly as the v5e's trace gave them in PR 25's traced run
# of gpt2m-train-dp1 (the first event of each kind on the XLA Ops line);
# ATTN is the same kernel as the parent commit named it (PR 24's trace)
FLASH_FWD = (
    "%flash_fwd.48 = (bf16[128,1024,64]{2,1,0:T(8,128)(2,1)}, "
    "f32[128,1024,1]{2,1,0:T(8,128)}) "
    "custom-call(bf16[128,1024,64]{2,1,0:T(8,128)(2,1)} "
    "%bitcast.3616, bf16[128,1024,64]{2,1,0:T(8,128)(2,1)S(1)} "
    "%bitcast.3568, bf16[128,1024,64]{2,1,0:T(8,128)(2,1)S(1)} "
    "%bitcast.3520), custom_call_target=\"tpu_custom_call\", "
    "operand_layout_constraints={bf16[128,1024,64]{2,1,0}, "
    "bf16[128,1024,64]{2,1,0}, bf16[128,1024,64]{2,1,0}}, "
    "frontend_attributes={kernel_metadata={}}")
FLASH_DQ = (
    "%flash_dq.24 = bf16[128,1024,64]{2,1,0:T(8,128)(2,1)} "
    "custom-call(bf16[128,1024,64]{2,1,0:T(8,128)(2,1)} "
    "%bitcast.3491, bf16[128,1024,64]{2,1,0:T(8,128)(2,1)S(1)} "
    "%bitcast.3485, bf16[128,1024,64]{2,1,0:T(8,128)(2,1)S(1)} "
    "%bitcast.3479, bf16[128,1024,64]{2,1,0:T(8,128)(2,1)S(1)} "
    "%bitcast.2938, f32[128,1024,1]{2,1,0:T(8,128)} "
    "%pallas_call.314, f32[128,1024,1]{2,1,0:T(8,128)} %copy.2557),"
    " custom_call_target=\"tpu_custom_call\", "
    "operand_layout_constraints={bf16[128,1024,64]{2,1,0}, "
    "bf16[128,1024,64]{2,1,0}, bf16[128,1024,64]{2,1,0}, "
    "bf16[128,1024,64]{2,1,0}, f32[128,1024,1]{2,1,0}, "
    "f32[128,1024,1]{2,1,0}}, "
    "frontend_attributes={kernel_metadata={}}")
FLASH_DKV = (
    "%flash_dkv.24 = (bf16[128,1024,64]{2,1,0:T(8,128)(2,1)}, "
    "bf16[128,1024,64]{2,1,0:T(8,128)(2,1)}) "
    "custom-call(bf16[128,1024,64]{2,1,0:T(8,128)(2,1)} "
    "%bitcast.3492, bf16[128,1024,64]{2,1,0:T(8,128)(2,1)S(1)} "
    "%bitcast.3486, bf16[128,1024,64]{2,1,0:T(8,128)(2,1)S(1)} "
    "%bitcast.3480, bf16[128,1024,64]{2,1,0:T(8,128)(2,1)S(1)} "
    "%bitcast.2939, f32[128,1024,1]{2,1,0:T(8,128)} "
    "%pallas_call.314, f32[128,1024,1]{2,1,0:T(8,128)} %copy.2557),"
    " custom_call_target=\"tpu_custom_call\", "
    "operand_layout_constraints={bf16[128,1024,64]{2,1,0}, "
    "bf16[128,1024,64]{2,1,0}, bf16[128,1024,64]{2,1,0}, "
    "bf16[128,1024,64]{2,1,0}, f32[128,1024,1]{2,1,0}, "
    "f32[128,1024,1]{2,1,0}}, "
    "frontend_attributes={kernel_metadata={}}")
COPY = (
    "%copy.2254 = bf16[1024,1024]{0,1:T(8,128)(2,1)S(1)} "
    "copy(bf16[1024,1024]{1,0:T(8,128)(2,1)S(1)} %fusion.1)")
ATTN = ("%attn.189 = (bf16[128,1024,64]{2,1,0:T(8,128)(2,1)S(1)}, f32[128,"
        "1024,1]{2,1,0:T(8,128)}) custom-call(bf16[128,1024,64]{2,1,0:T(8,"
        "128)(2,1)} %bitcast.3271), custom_call_target=\"tpu_custom_call\"")
STEP = "jit_train_step(7788)"


def _win(ops, runs=2):
    """A window of ``runs`` whole 100 ns runs of the step from t=100, the
    trace's own first and last run already dropped."""
    return {"modules": [(STEP, 100 * (i + 1), 100) for i in range(runs)],
            "ops": ops, "asyncs": []}


def test_kernel_time_by_the_name_the_program_gave_it():
    ops = [(FLASH_FWD, 100, 10), (FLASH_DQ, 110, 4), (FLASH_DKV, 114, 6),
           (FLASH_FWD, 120, 10), (COPY, 130, 3), (FLASH_FWD, 200, 10),
           (FLASH_DQ, 210, 4), (FLASH_DKV, 214, 8), (FLASH_FWD, 222, 12)]
    r = SimpleNamespace(win=_win(ops))
    read = lambda s: named.op_ms_per_run(r, "train_step", s)
    assert read("flash_fwd") == pytest.approx(21e-6)
    assert read("flash_dq") == pytest.approx(4e-6)
    assert read("flash_dkv") == pytest.approx(7e-6)
    # the three are the custom calls the older metrics sum by their target
    assert (read("flash_fwd") + read("flash_dq") + read("flash_dkv")) * 2e6 \
        == xplane.kernel_ns(ops, "custom-call", "tpu_custom_call")
    # an operand named after a kernel does not make another operation one
    assert "%pallas_call.314" in FLASH_DQ and read("pallas_call") is None
    assert read("copy") == pytest.approx(1.5e-6)


def test_events_outside_the_whole_runs_do_not_count():
    ops = [(FLASH_FWD, 50, 20), (FLASH_FWD, 150, 20), (FLASH_FWD, 290, 20)]
    r = SimpleNamespace(win=_win(ops))
    # 150..170 whole, 290..300 of the last: 30 ns over two runs
    assert named.op_ms_per_run(r, "train_step", "flash_fwd") == \
        pytest.approx(15e-6)


@pytest.mark.parametrize("win", [
    None,                                       # no trace
    _win([(ATTN, 100, 10), (COPY, 110, 5)]),    # the parent: no such name
    {"modules": [("jit__decode_raw(1)", 100, 100)],
     "ops": [(FLASH_FWD, 100, 10)], "asyncs": []},   # no run of the step
])
def test_a_reader_that_finds_nothing_returns_none(win):
    r = SimpleNamespace(win=win)
    assert named.op_ms_per_run(r, "train_step", "flash_fwd") is None


SNAPSHOT = {
    "counters": {
        "jax_compile_seconds_total": [
            {"labels": {"fun": "train_step", "phase": "trace"}, "value": 9.0},
            {"labels": {"fun": "train_step", "phase": "lower"}, "value": 7.0},
            {"labels": {"fun": "train_step", "phase": "backend"},
             "value": 40.0},
            {"labels": {"fun": "other", "phase": "trace"}, "value": 3.0}],
        "jax_compile_total": [
            {"labels": {"fun": "train_step", "phase": "trace"}, "value": 3},
            {"labels": {"fun": "train_step", "phase": "lower"}, "value": 2},
            {"labels": {"fun": "other", "phase": "lower"}, "value": 90}]},
    "gauges": {
        "grad_sync_bytes": [
            {"labels": {"program": "train_step",
                        "scope": "hvd/optimizer/sync"}, "value": 1.5e9},
            {"labels": {"program": "train_step",
                        "scope": "hvd/value_and_grad/sync"}, "value": 1.5e9},
            {"labels": {"program": "eval_step", "scope": "none"},
             "value": 7e9}],
        "import_seconds": [{"labels": {}, "value": 1.25}]},
    "histograms": {
        "init_seconds": [{"labels": {}, "count": 2, "sum": 0.75,
                          "buckets": []}]},
}


@pytest.fixture()
def program(monkeypatch):
    """A stand-in for the program whose registry holds SNAPSHOT."""
    fake = SimpleNamespace(metrics=SimpleNamespace(
        snapshot=lambda: json.loads(json.dumps(SNAPSHOT))))
    monkeypatch.setitem(sys.modules, "horovod_tpu", fake)
    return fake


def _args(metric):
    with open(os.path.join(BENCH, "layer_metrics", metric + ".json")) as f:
        return json.load(f)["args"]


def test_registry_series_by_name_and_label_match(program):
    r = SimpleNamespace()
    assert named.series_total(r, **_args("grad_sync_mb.train")) == \
        pytest.approx(3000.0)
    # seconds of trace and lower, over the lowerings
    assert named.series_total(r, **_args("step_trace_lower_s.train")) == \
        pytest.approx(8.0)
    # a gauge plus a histogram's sum
    assert named.series_total(r, **_args("program_import_init_s")) == \
        pytest.approx(2.0)


def test_registry_without_the_series_reads_none(program):
    r = SimpleNamespace()
    assert named.series_total(r, [{"name": "no_such_series"}]) is None
    assert named.series_total(
        r, [{"name": "grad_sync_bytes", "labels": {"program": "nope"}}]) \
        is None
    assert named.series_total(
        r, [{"name": "import_seconds"}],
        per=[{"name": "jax_compile_total", "labels": {"fun": "nope"}}]) \
        is None
    # a sum that lacks one of its parts is not the metric: the parent of
    # PR 25 has init_seconds and no import_seconds
    older = json.loads(json.dumps(SNAPSHOT))
    del older["gauges"]["import_seconds"]
    program.metrics.snapshot = lambda: older
    assert named.series_total(r, **_args("program_import_init_s")) is None
    # the serving metrics' series are not in a training run's registry
    assert named.series_total(r, **_args("engine_host_ms.serve")) is None
    assert named.series_total(r, **_args("engine_readback_ms.serve")) is None


def test_the_programs_real_registry_is_read():
    sys.path.insert(0, ROOT)
    import horovod_tpu as hvd
    hvd.init()                          # init_seconds, as every driver has
    hvd.metrics.gauge("grad_sync_bytes", program="probe_step",
                      scope="hvd/optimizer/sync").set(5e6)
    hvd.metrics.counter("serve_step_phase_seconds_total", engine="probe",
                        phase="commit").inc(0.004)
    hvd.metrics.counter("serve_step_phase_seconds_total", engine="probe",
                        phase="dispatch").inc(0.5)
    hvd.metrics.counter("serve_step_phase_total", engine="probe",
                        phase="dispatch").inc(2)
    r = SimpleNamespace()
    assert named.series_total(
        r, [{"name": "grad_sync_bytes",
             "labels": {"program": "probe_step"}}], scale=1e-6) == \
        pytest.approx(5.0)
    host = _args("engine_host_ms.serve")
    for sel in host["series"] + host["per"]:
        sel["labels"]["engine"] = "probe"
    assert named.series_total(r, **host) == pytest.approx(2.0)
    assert named.series_total(r, **_args("program_import_init_s")) > 0


NEW = ["flash_fwd_ms.train", "flash_dq_ms.train", "flash_dkv_ms.train",
       "grad_sync_mb.train", "step_trace_lower_s.train",
       "program_import_init_s", "engine_host_ms.serve",
       "engine_readback_ms.serve"]


@pytest.mark.parametrize("metric", NEW)
def test_each_new_file_agrees_with_its_entry_and_its_reader(metric):
    with open(os.path.join(BENCH, "layer_metrics", metric + ".json")) as f:
        spec = json.load(f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert spec["name"] == metric
    module, function = spec["reader"].split(":")
    assert module == "named" and callable(getattr(named, function))
    entry = [m for m in bench["per_layer"] if m["name"] == metric]
    if metric.endswith(".serve"):       # waits with the serving cell
        assert entry == [] and spec["workloads"] == ["gpt2m-serve-closed8"]
        return
    assert len(entry) == 1
    for key in ("unit", "layer", "moves", "source", "better"):
        assert spec[key] == entry[0][key], key
    assert spec.get("workloads") == entry[0].get("workloads")
    assert entry[0]["layer"] in {m["layer"] for m in bench["per_layer"][:7]}
    assert entry[0]["moves"] in {m["name"] for m in bench["end_to_end"]}
