"""Reduce a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read.

Everything but :func:`load` is a pure function over event lists, an event
being ``(name, start_ns, dur_ns)``, so ``benchmark/tests/test_reduce.py``
checks each on synthetic lists. What one v5e chip's trace looks like (seen
on the chip, PERF.md section 3): planes ``/device:TPU:<n>`` with the lines
``XLA Modules`` (one event per program run, ``jit_train_step(<hash>)``) and
``XLA Ops`` (one event per HLO instruction run; the name is the
instruction's HLO text), ``Async XLA Ops`` (start-to-done spans of
asynchronous instructions), and the plane ``/host:CPU`` whose ``python3``
line holds ``jax.profiler.TraceAnnotation`` spans on the same clock.
"""

import re

COLLECTIVE_OPCODES = ("all-reduce", "all-gather", "reduce-scatter",
                      "all-to-all", "collective-permute",
                      "collective-broadcast")
_TRAILING_ID = re.compile(r"[.\d]+$")


def hlo_opcode(name):
    """The HLO opcode of an ``XLA Ops`` event.

    ``%attn.189 = (bf16[...], f32[...]) custom-call(...)`` gives
    ``custom-call``; a bare instruction name such as ``fusion.10`` (other
    backends write those) gives ``fusion``."""
    if " = " not in name:
        return _TRAILING_ID.sub("", name.lstrip("%"))
    rest = name.split(" = ", 1)[1].lstrip()
    if rest.startswith("("):            # a tuple shape: skip to its close
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                rest = rest[i + 1:].lstrip()
                break
    else:                               # "f32[8,1023]{1,0:T(8,128)} fusion("
        rest = rest.split(" ", 1)[1] if " " in rest else rest
    return rest.split("(", 1)[0].strip()


def instruction_name(name):
    """``%all-reduce-start.3 = ...`` gives ``all-reduce-start.3``."""
    return name.split(" = ", 1)[0].strip().lstrip("%")


def is_collective(name):
    """True for a collective instruction, its asynchronous start and done
    halves included, and for a fusion the compiler named after one."""
    return (hlo_opcode(name).startswith(COLLECTIVE_OPCODES)
            or instruction_name(name).startswith(COLLECTIVE_OPCODES))


def clip(events, lo, hi):
    """The parts of ``events`` inside ``[lo, hi)``."""
    out = []
    for name, start, dur in events:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            out.append((name, a, b - a))
    return out


def union(events):
    """Sorted, disjoint ``[start, end)`` intervals covering ``events``."""
    spans = sorted((s, s + d) for _, s, d in events if d > 0)
    out = []
    for a, b in spans:
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def total(intervals):
    return sum(b - a for a, b in intervals)


def subtract(intervals, holes):
    """``intervals`` minus ``holes``; both sorted and disjoint."""
    out, j = [], 0
    for a, b in intervals:
        while j < len(holes) and holes[j][1] <= a:
            j += 1
        k = j
        while k < len(holes) and holes[k][0] < b:
            if holes[k][0] > a:
                out.append((a, holes[k][0]))
            a = max(a, holes[k][1])
            k += 1
        if a < b:
            out.append((a, b))
    return out


def busy_ns(op_events):
    """Nanoseconds in which at least one operation ran."""
    return total(union(op_events))


def idle_share(op_events, window_ns):
    """1 - busy / window. The caller clips the events to the window."""
    return 1.0 - busy_ns(op_events) / window_ns


def collective_ns(op_events, async_events=()):
    """``(all, exposed)`` nanoseconds of collectives on one device: the
    union of collective instructions on the op line and of collective
    spans on the asynchronous line, and the part of that union during
    which no other instruction ran."""
    coll = union([e for e in op_events if is_collective(e[0])]
                 + [e for e in async_events if is_collective(e[0])])
    compute = union([e for e in op_events if not is_collective(e[0])])
    return total(coll), total(subtract(coll, compute))


def kernel_ns(op_events, opcode, contains=""):
    """Summed duration of the events with this opcode whose HLO text holds
    ``contains``."""
    return sum(d for n, _, d in op_events
               if hlo_opcode(n) == opcode and contains in n)


def module_stats(module_events, substring):
    """Runs of the programs whose name holds ``substring``: count, total
    and mean nanoseconds. None when no run matched."""
    hit = [d for n, _, d in module_events if substring in n]
    if not hit:
        return None
    return {"count": len(hit), "total_ns": sum(hit),
            "mean_ns": sum(hit) / len(hit)}


_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def op_group(name):
    """The name an instruction is summed under: its own HLO text, except
    that the custom calls of one target and one base name (the flash
    kernels are ``%attn.120`` .. ``%attn.189``) are one group."""
    if hlo_opcode(name) != "custom-call":
        return name
    target = _TARGET.search(name)
    base = _TRAILING_ID.sub("", instruction_name(name))
    return (f"custom-call {target.group(1) if target else '?'} "
            f"(%{base}.*, all instances)")


def top_ops(op_events, n=10):
    """The ``n`` instruction groups with the most summed time."""
    tot = {}
    for name, _, dur in op_events:
        key = op_group(name)
        tot[key] = tot.get(key, 0) + dur
    return sorted(tot.items(), key=lambda kv: -kv[1])[:n]


def idle_gaps(op_events, lo, hi, host_spans=(), module_events=(), n=10):
    """The ``n`` longest idle gaps of the device inside ``[lo, hi)``, each
    named by the host span that covers most of it, else "inside the
    program" where a program run covers its middle, else "no bench span".
    Returns ``[(label, ns), ...]``, longest first."""
    gaps = subtract([(lo, hi)], union(op_events))
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:n]
    out = []
    for a, b in gaps:
        best, best_ns = None, 0
        for name, s, d in host_spans:
            ov = min(b, s + d) - max(a, s)
            if ov > best_ns:
                best, best_ns = name, ov
        if best is None:
            mid = (a + b) / 2
            inside = any(s <= mid < s + d for _, s, d in module_events)
            best = "inside the program" if inside else "no bench span"
        out.append((best, b - a))
    return out


# --------------------------------------------------------------------------
# loading
# --------------------------------------------------------------------------

def load(path, host_prefix="bench:"):
    """Read an ``.xplane.pb`` with JAX alone. Returns ``{"devices":
    {plane: {line: [event, ...]}}, "host": [event, ...]}`` where ``host``
    holds the spans whose name starts with ``host_prefix``, from any
    thread of the host plane."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, host = {}, []
    first = min((p.name for p in data.planes
                 if p.name.startswith("/device:TPU:")), default=None)
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            # every line of the first device; of the others only the
            # operations, for their busy time
            devices[plane.name] = {
                line.name: [(e.name, int(e.start_ns), int(e.duration_ns))
                            for e in line.events]
                for line in plane.lines
                if plane.name == first or line.name == "XLA Ops"}
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                host.extend((e.name, int(e.start_ns), int(e.duration_ns))
                            for e in line.events
                            if e.name.startswith(host_prefix))
    return {"devices": devices, "host": host}


def window(trace, window_span="bench:traced"):
    """The traced window of a loaded trace: its bounds (the longest host
    span named ``window_span``), the first device's events clipped to it,
    the other host spans, and every device's busy seconds. None when the
    trace holds no such span or no device."""
    spans = [e for e in trace["host"] if e[0] == window_span]
    if not spans or not trace["devices"]:
        return None
    _, lo, dur = max(spans, key=lambda e: e[2])
    hi = lo + dur
    busy = {plane: busy_ns(clip(lines.get("XLA Ops", ()), lo, hi)) / 1e9
            for plane, lines in sorted(trace["devices"].items())}
    first = sorted(trace["devices"])[0]
    lines = trace["devices"][first]
    return {
        "device": first, "lo": lo, "hi": hi, "window_s": (hi - lo) / 1e9,
        "busy_s_per_device": busy,
        "ops": clip(lines.get("XLA Ops", ()), lo, hi),
        "asyncs": clip(lines.get("Async XLA Ops", ()), lo, hi),
        # Whole program runs only. The first and the last run the trace
        # holds may be cut by its own start and stop (seen on the v5e: a
        # 1.2 ms stub of a 257 ms step), so they never count as whole.
        "modules": [e for e in sorted(lines.get("XLA Modules", ()),
                                      key=lambda e: e[1])[1:-1]
                    if e[1] >= lo and e[1] + e[2] <= hi],
        "all_modules": list(lines.get("XLA Modules", ())),
        "host": [e for e in trace["host"] if e[0] != window_span],
    }


def whole_runs(win, substring):
    """The window cut down to the whole runs of the programs whose name
    holds ``substring``: ``(runs, ops, asyncs)`` with the events clipped to
    first start .. last end. None when no whole run matched."""
    runs = sorted((e for e in win["modules"] if substring in e[0]),
                  key=lambda e: e[1])
    if not runs:
        return None
    lo, hi = runs[0][1], runs[-1][1] + runs[-1][2]
    return runs, clip(win["ops"], lo, hi), clip(win["asyncs"], lo, hi)


def summarize(win, top=10, name_chars=160):
    """The window as one small dictionary for the log and the breakdown."""
    busy = win["busy_s_per_device"]
    coll, exposed = collective_ns(win["ops"], win["asyncs"])
    by_module = {}
    for name, _, d in win["modules"]:
        m = by_module.setdefault(name.split("(", 1)[0],
                                 {"count": 0, "total_s": 0.0})
        m["count"] += 1
        m["total_s"] += d / 1e9
    for m in by_module.values():
        m["mean_s"] = m["total_s"] / m["count"]
    return {
        "device": win["device"],
        "window_s": win["window_s"],
        "busy_s": busy[win["device"]],
        "busy_s_per_device": busy,
        "busy_s_mean": sum(busy.values()) / len(busy),
        "op_events": len(win["ops"]),
        "collective_s": coll / 1e9,
        "collective_exposed_s": exposed / 1e9,
        "modules": by_module,
        "device_ops": [[n[:name_chars], d / 1e9]
                       for n, d in top_ops(win["ops"], top)],
        "idle_gaps": [[label, ns / 1e9] for label, ns in
                      idle_gaps(win["ops"], win["lo"], win["hi"],
                                win["host"], win["all_modules"], top)],
    }
