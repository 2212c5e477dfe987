#!/usr/bin/env python3
"""The benchmark's command: one run of one cell.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is data found by name (``benchmark/README.md``):
``BENCHMARK.json`` names the cell's configuration and traffic mix, the
traffic file names its driver, each per-layer metric's file names its
reader. This file knows none of them. It checks the device, sets the run
up, measures for ``--seconds``, checks the outputs, reduces the trace and
prints one JSON object as the last line of standard output.

The run needs a TPU and fails without one. ``JAX_PLATFORMS=cpu`` turns it
into a rehearsal of the code at whatever size the cell has: the last line
then names the platform ``cpu`` and its ``metrics`` are empty, because a CPU
timing is never written under a device metric's name.
"""

import time

_T_START = time.perf_counter()          # set-up counts from process start

import argparse                         # noqa: E402
import importlib                        # noqa: E402
import json                             # noqa: E402
import os                               # noqa: E402
import shutil                           # noqa: E402
import sys                              # noqa: E402
from types import SimpleNamespace       # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


def log(msg):
    """One line of the run's log, stamped with the seconds since the
    process started, so that a slow set-up shows where it went."""
    print(f"[{time.perf_counter() - _T_START:6.1f}] {msg}", flush=True)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def by_name(entries, name, what):
    for entry in entries:
        if entry["name"] == name:
            return entry
    raise SystemExit(f"run.py: no {what} named {name!r} in BENCHMARK.json")


def applies(metric, cell_name):
    return cell_name in metric.get("workloads", [cell_name])


class CompileCounter:
    """Programs handed to the backend, how many the persistent cache
    answered, and the seconds spent there, from jax's monitoring events."""

    def __init__(self, jax):
        self.programs = 0
        self.cache_hits = 0
        self.seconds = 0.0
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def _on_event(self, name, **kw):
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def _on_duration(self, name, secs, **kw):
        if name == "/jax/core/compile/backend_compile_duration":
            self.programs += 1
            self.seconds += secs


class Tracer:
    """Traces ``length_s`` seconds of the window, starting ``after_s`` into
    it, when the run was started with ``--trace 1``. The driver calls
    :meth:`tick` as the window goes; :meth:`span` marks what the host is
    doing, on the profiler's clock."""

    def __init__(self, jax, enabled, after_s, length_s, out_dir):
        self._jax = jax
        self.enabled = enabled
        self.after_s, self.length_s = after_s, length_s
        self.out_dir = out_dir
        self._window = None
        self._from = None
        self.done = False

    def span(self, name):
        return self._jax.profiler.TraceAnnotation(name)

    def tick(self, elapsed):
        if not self.enabled or self.done:
            return
        if self._window is None:
            if elapsed >= self.after_s:
                self._jax.profiler.start_trace(self.out_dir)
                self._window = self.span("bench:traced")
                self._window.__enter__()
                self._from = time.perf_counter()
        elif time.perf_counter() - self._from >= self.length_s:
            self.stop()

    def stop(self):
        if self._window is not None and not self.done:
            self._window.__exit__(None, None, None)
            self._jax.profiler.stop_trace()
            self.done = True

    def file(self):
        """The trace written, or None."""
        for base, _, names in os.walk(self.out_dir):
            for name in names:
                if name.endswith(".xplane.pb"):
                    return os.path.join(base, name)
        return None


def dir_bytes(path):
    total = 0
    for base, _, names in os.walk(path):
        for name in names:
            try:
                total += os.path.getsize(os.path.join(base, name))
            except OSError:
                pass
    return total


def read_layer_metrics(bench, cell, rctx):
    """Every per-layer metric of this cell through its own reader. A reader
    that finds nothing to read returns None and the metric is left out."""
    out = {}
    for metric in bench["per_layer"]:
        if not applies(metric, cell["name"]):
            continue
        spec = load_json(BENCH, "layer_metrics", metric["name"] + ".json")
        module, function = spec["reader"].split(":")
        reader = getattr(importlib.import_module("readers." + module),
                         function)
        value = reader(rctx, **spec.get("args", {}))
        if value is not None:
            out[metric["name"]] = {"value": float(value),
                                   "unit": metric["unit"]}
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    bench = load_json(ROOT, "BENCHMARK.json")
    cell = by_name(bench["workloads"], args.workload, "workload")
    config_entry = by_name(bench["configs"], cell["config"], "config")
    config = load_json(ROOT, config_entry["file"])
    traffic = load_json(BENCH, "traffic", cell["traffic"] + ".json")
    chips = int(cell["chips"])

    rehearsal = os.environ.get("JAX_PLATFORMS", "").strip() == "cpu"
    if os.path.isdir(os.path.join(ROOT, "horovod_tpu")):
        sys.path.insert(0, ROOT)
    import jax
    import horovod_tpu as hvd
    from horovod_tpu.utils import compile_cache

    cache_dir = compile_cache.enable()
    compiles = CompileCounter(jax)
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    log(f"jax {jax.__version__}; platform {device['platform']}, device_kind "
        f"{device['kind']!r}, {device['count']} device(s); compile cache "
        f"{cache_dir}")
    if device["platform"] != "tpu" and not rehearsal:
        print(f"run.py: no TPU (platform {device['platform']!r}) and "
              "JAX_PLATFORMS is not 'cpu': nothing was run", file=sys.stderr)
        return 2
    if len(devices) < chips:
        print(f"run.py: cell {cell['name']} needs {chips} chip(s), JAX sees "
              f"{len(devices)}: nothing was run", file=sys.stderr)
        return 2
    if rehearsal:
        log("CPU REHEARSAL: this run says nothing about the chip; its last "
            "line carries no metric")
        peak = None
    else:
        peaks = load_json(BENCH, "peaks.json")
        if device["kind"] not in peaks:
            raise SystemExit(f"run.py: no peaks for device kind "
                             f"{device['kind']!r} in benchmark/peaks.json")
        peak = peaks[device["kind"]]
    log(f"cell {cell['name']}: config {cell['config']}, traffic "
        f"{cell['traffic']} (driver {traffic['driver']}), {chips} chip(s), "
        f"seed {args.seed}, {args.seconds} s, trace {args.trace}")

    trace_dir = os.path.join(TRACE_DIR, cell["name"])
    shutil.rmtree(trace_dir, ignore_errors=True)
    after_s = min(traffic.get("trace_after_s", 1.0), 0.2 * args.seconds)
    length_s = min(traffic.get("trace_s", 3.0), 0.5 * args.seconds)
    tracer = Tracer(jax, bool(args.trace), after_s, length_s, trace_dir)
    ctx = SimpleNamespace(
        jax=jax, hvd=hvd, cell=cell, config=config, traffic=traffic,
        family=importlib.import_module("families." + config["model"]),
        devices=devices[:chips], seed=args.seed, trace=bool(args.trace),
        tracer=tracer, compiles=compiles, rehearsal=rehearsal, log=log)
    driver = importlib.import_module("drivers." + traffic["driver"])

    state = driver.set_up(ctx)
    setup_s = time.perf_counter() - _T_START
    setup_programs, setup_compile_s = compiles.programs, compiles.seconds
    log(f"set-up {setup_s:.1f} s: {compiles.programs} programs, "
        f"{compiles.cache_hits} from the persistent cache, "
        f"{compiles.seconds:.1f} s in the backend")

    try:
        result = driver.window(ctx, state, args.seconds)
    finally:
        tracer.stop()
    in_window = compiles.programs - setup_programs
    log(f"window: {result['attempted']} attempted, {result['failed']} "
        f"failed; programs compiled in the window: {in_window}")
    # the allocator's peak is read before the reference check allocates
    stats = [d.memory_stats() or {} for d in ctx.devices]
    device["memory_peak_bytes"] = max(
        int(s.get("peak_bytes_in_use", 0)) for s in stats)

    t0 = time.perf_counter()
    checked = driver.check(ctx, state)
    log(f"reference check: {'passed' if checked else 'FAILED'} in "
        f"{time.perf_counter() - t0:.1f} s")
    correct = bool(checked and in_window == 0 and result["failed"] == 0
                   and result.get("steady", True))

    counters = dict(result.get("counters", {}))
    counters["compile_s"] = setup_compile_s
    end_to_end = dict(result["end_to_end"], setup_s=setup_s)
    metrics = {}
    out = {"correct": correct, "attempted": int(result["attempted"]),
           "failed": int(result["failed"])}
    if args.trace:
        win = summary = None
        path = tracer.file()
        if path is not None:
            from reduce import xplane
            t0 = time.perf_counter()
            size = os.path.getsize(path)
            win = xplane.window(xplane.load(path))
            if win is not None:
                summary = xplane.summarize(win)
            log(f"trace: {size / 1e6:.1f} MB reduced in "
                f"{time.perf_counter() - t0:.1f} s")
        shutil.rmtree(trace_dir, ignore_errors=True)
        if summary is not None:
            log("trace: " + json.dumps(
                {k: v for k, v in summary.items()
                 if k not in ("device_ops", "idle_gaps")}))
            if not rehearsal:
                device["busy_s"] = summary["busy_s_mean"]
                device["window_s"] = summary["window_s"]
                out["breakdown"] = {"device_ops": summary["device_ops"],
                                    "idle_gaps": summary["idle_gaps"]}
        rctx = SimpleNamespace(win=win, summary=summary, counters=counters,
                               cell=cell, config=config, traffic=traffic,
                               chips=chips, peak=peak, family=ctx.family)
        metrics = read_layer_metrics(bench, cell, rctx)
    else:
        for metric in bench["end_to_end"]:
            if applies(metric, cell["name"]):
                metrics[metric["name"]] = {
                    "value": float(end_to_end[metric["name"]]),
                    "unit": metric["unit"]}
    log(f"compile: {compiles.programs} programs, {compiles.cache_hits} from "
        f"the persistent cache, {compiles.seconds:.1f} s in the backend; "
        f"cache directory {dir_bytes(cache_dir) / 1e6:.1f} MB; whole run "
        f"{time.perf_counter() - _T_START:.1f} s")
    if rehearsal:
        log("rehearsal values (CPU, not metrics): " + json.dumps(metrics))
        metrics = {}
    out.update(metrics=metrics, device=device, workload=cell["name"],
               seed=args.seed, trace=args.trace)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
