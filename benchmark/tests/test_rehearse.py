"""``run.py`` end to end on the CPU, at tiny sizes, for each driver.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_rehearse.py -q

The tiny cells are throw-away: each test copies ``benchmark/`` into a temp
directory and adds a configuration file, a traffic file and the entries of
``BENCHMARK.json`` that name them (for the serving cell, which
``BENCHMARK.json`` may not hold yet, its metrics' entries too, from their own
files), and edits nothing that was there. That it
then runs is the proof that a cell, a configuration and a traffic mix need
new files and new entries only. Not part of the repo's tier-1 tests.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
TINY = dict(n_layer=2, n_embd=64, n_head=4, n_positions=128, n_ctx=128,
            vocab_size=250)


def _load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def _dump(obj, *parts):
    with open(os.path.join(*parts), "w") as f:
        json.dump(obj, f)


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """A directory that holds only BENCHMARK.json and the files under its
    paths, plus the new files of three throw-away cells."""
    tmp = str(tmp_path_factory.mktemp("bench"))
    shutil.copytree(BENCH, os.path.join(tmp, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = _load(ROOT, "BENCHMARK.json")

    def add_cell(name, suffix, end_to_end, config, traffic, chips):
        """New entries only: the cell, its files, its name on the metrics
        it reports. A metric that BENCHMARK.json does not hold yet is
        entered from its own file (per-layer) or as given (end-to-end)."""
        bench["configs"].append({
            "name": name, "source": "test", "reduced": [], "why": "test",
            "file": f"benchmark/configs/{name}.json"})
        _dump(config, tmp, "benchmark", "configs", name + ".json")
        _dump(traffic, tmp, "benchmark", "traffic", name + ".json")
        bench["workloads"].append({"name": name, "config": name,
                                   "traffic": name, "chips": chips,
                                   "why": "test"})
        have = {m["name"]: m for m in bench["end_to_end"]}
        for metric, unit in end_to_end:
            if metric not in have:
                bench["end_to_end"].append(
                    {"name": metric, "unit": unit, "better": "lower",
                     "bound": 0.1, "source": "host_clock", "workloads": []})
                have[metric] = bench["end_to_end"][-1]
            have[metric]["workloads"].append(name)
        have = {m["name"]: m for m in bench["per_layer"]}
        for file in sorted(os.listdir(os.path.join(BENCH, "layer_metrics"))):
            if not file.endswith(suffix + ".json"):
                continue
            spec = _load(BENCH, "layer_metrics", file)
            if spec["name"] not in have:
                bench["per_layer"].append(
                    {k: spec[k] for k in ("name", "unit", "better", "source",
                                          "layer", "moves")})
                bench["per_layer"][-1]["workloads"] = []
                have[spec["name"]] = bench["per_layer"][-1]
            have[spec["name"]]["workloads"].append(name)

    train = _load(BENCH, "configs", "gpt2-medium-train.json")
    train.update(TINY)
    train["assumed"]["vocab_padded"] = 256
    steps = _load(BENCH, "traffic", "train-fixed-8x1024.json")
    steps.update(sequences_per_chip=2, seq_len=64)
    e2e = [("train_tokens_per_s_chip", "tokens/s/chip")]
    add_cell("tiny-train-dp1", ".train", e2e, train, steps, 1)
    add_cell("tiny-train-dp4", ".train", e2e, train, steps, 4)
    serve = _load(BENCH, "configs", "gpt2-medium-serve.json")
    serve.update(TINY)
    serve["assumed"]["vocab_padded"] = 256
    serve["run"]["engine"].update(slots=4, max_len=96, block_size=4)
    closed = _load(BENCH, "traffic", "closed8-short-chat.json")
    closed.update(
        callers=4, pool=16, check_pad_len=64,
        prompt_len={"median": 8, "sigma": 0.8, "min": 2, "max": 40},
        output_len={"median": 6, "sigma": 0.6, "min": 2, "max": 16})
    add_cell("tiny-serve-closed4", ".serve",
             [("serve_tokens_per_s", "tokens/s"), ("itl_p95_ms", "ms"),
              ("ttft_p90_ms", "ms")], serve, closed, 1)
    _dump(bench, tmp, "BENCHMARK.json")
    return tmp


def _run(cwd, workload, trace, env=None, pythonpath=ROOT):
    full = {k: v for k, v in os.environ.items()
            if k not in ("JAX_PLATFORMS", "XLA_FLAGS", "PYTHONPATH")}
    full.update(env or {})
    if pythonpath:
        full["PYTHONPATH"] = pythonpath
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", workload,
         "--seed", "3000000019", "--seconds", "2", "--trace", str(trace)],
        cwd=cwd, env=full, capture_output=True, text=True, timeout=600)


CPU = {"JAX_PLATFORMS": "cpu",
       "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}


@pytest.mark.parametrize("workload,trace", [
    ("tiny-train-dp1", 0), ("tiny-train-dp4", 1), ("tiny-serve-closed4", 0),
    ("tiny-serve-closed4", 1)])
def test_rehearsal_on_the_cpu(checkout, workload, trace):
    done = _run(checkout, workload, trace, CPU)
    assert done.returncode == 0, done.stdout[-3000:] + done.stderr[-3000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert set(last) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    assert last["device"]["platform"] == "cpu"
    assert last["device"]["count"] == 4
    # a CPU run carries no device metric, and no device times
    assert last["metrics"] == {}
    assert "busy_s" not in last["device"] and "breakdown" not in last
    assert "programs compiled in the window: 0" in done.stdout


def test_no_tpu_and_no_cpu_override_is_an_error(checkout):
    """Without JAX_PLATFORMS=cpu a machine with no TPU must not fall back."""
    done = _run(checkout, "tiny-train-dp1", 0)
    assert done.returncode != 0
    assert "{" not in (done.stdout.strip().splitlines() or [""])[-1]


def test_more_chips_asked_than_present_is_an_error(checkout):
    done = _run(checkout, "tiny-train-dp4", 0, {"JAX_PLATFORMS": "cpu"})
    assert done.returncode != 0
    assert "needs 4 chip(s)" in done.stderr


def test_checkout_without_the_program_is_an_error(checkout):
    done = _run(checkout, "tiny-train-dp1", 0, CPU, pythonpath=None)
    assert done.returncode != 0 and "horovod_tpu" in done.stderr


def test_metric_files_agree_with_benchmark_json():
    bench = _load(ROOT, "BENCHMARK.json")
    cells = {c["name"] for c in bench["workloads"]}
    e2e = {m["name"] for m in bench["end_to_end"]}
    for metric in bench["per_layer"]:
        spec = _load(BENCH, "layer_metrics", metric["name"] + ".json")
        for key in ("unit", "layer", "moves", "source", "better"):
            assert spec[key] == metric[key], (metric["name"], key)
        assert metric["moves"] in e2e
        assert set(metric.get("workloads", cells)) <= cells
        module, function = spec["reader"].split(":")
        assert os.path.exists(os.path.join(BENCH, "readers", module + ".py"))
