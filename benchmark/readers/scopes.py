"""Device time by the program's own scopes.

The program says which compiled instruction lies in which of its scopes
(``hvd.tracing.scope_table(program)``: ``{instruction name: row}`` with
``scopes`` outermost first, ``layer``, ``direction``, ``container``,
``kernel``, ``op_name``); the trace says how long each instruction ran. The
join and the sums are here. A program without such a table (an older commit)
reads as None, and the metric is left out of the line.

Two rules of the table shape every number (``horovod_tpu/tracing.py`` states
them): a fusion's scope is its root's, and an instruction whose name holds a
kernel name of the program (``flash_fwd``, ``ragged-dot``) is never unscoped.
A container (``while``, ``conditional``, ``call``) is an event over the events
of its body and is skipped, so that nothing counts twice.

The first reading of a run prints the whole table, scope by direction, in
ms per run: ``PERF.md`` section 5 is written from those lines.
"""

import re

from reduce import xplane

_TRAILING_ID = re.compile(r"[.\d]+$")
_NUMBER = re.compile(r"\d+")
NO_SCOPE = "no scope of ours"
UNKNOWN = "not in the table"


def join(win, module, table):
    """The whole runs of ``module`` on the first device against ``table``:
    ``(runs, busy_ns, events)`` with one ``(row or None, text, ns)`` for
    each event that is no container; ``row`` is None where the table does
    not hold the event's instruction name. None when no run is whole."""
    cut = xplane.whole_runs(win, module)
    if cut is None:
        return None
    runs, ops, _ = cut
    events = []
    for text, _, ns in ops:
        row = table.get(xplane.instruction_name(text))
        if row is None or not row.container:
            events.append((row, text, ns))
    return runs, xplane.busy_ns(ops), events


def _home(row):
    """The one line of the printed table an event counts under: its
    innermost scope; a kernel in no scope under its own name."""
    if row is None:
        return UNKNOWN
    if row.scopes:
        return row.scopes[-1]
    return f"(kernel) {row.kernel}" if row.kernel else NO_SCOPE


def table_lines(module, runs, busy_ns, events, groups=10):
    """The joined runs as ``PERF.md`` section 5 prints them: ms per run of
    every scope by direction, each event under its innermost scope (so the
    lines add up to the device's time), and beside it the time of all a
    scope holds, nested scopes included; then the largest instruction
    groups in no scope."""
    per = len(runs) * 1e6
    own, nested, loose = {}, {}, {}
    for row, text, ns in events:
        line = own.setdefault(_home(row), {"fwd": 0, "remat": 0, "bwd": 0})
        line[row.direction if row is not None else "fwd"] += ns
        for scope in (row.scopes if row is not None else ()):
            nested[scope] = nested.get(scope, 0) + ns
        if row is None or row.layer is None:
            name = xplane.instruction_name(text)
            key = (_TRAILING_ID.sub("", name),
                   _NUMBER.sub("N", row.op_name) if row is not None
                   else UNKNOWN)
            group = loose.setdefault(key, [0, 0, text])
            group[0] += ns
            group[1] += 1
    total = sum(sum(line.values()) for line in own.values())
    unknown = sum(own.get(UNKNOWN, {}).values())
    out = [f"scopes: {module}, {len(runs)} whole runs on the first device, "
           f"{busy_ns / per:.2f} ms busy a run; ms a run, each instruction "
           "under its innermost scope, containers skipped",
           f"scopes: {'scope':32s} {'fwd':>8s} {'remat':>8s} {'bwd':>8s} "
           f"{'sum':>8s} {'% busy':>7s} {'all it holds':>12s}"]
    last = (NO_SCOPE, UNKNOWN)
    for name in sorted((n for n in own if n not in last),
                       key=lambda n: -sum(own[n].values())) + [
                           n for n in last if n in own]:
        line = own[name]
        whole = sum(line.values())
        held = f"{nested[name] / per:12.2f}" if name in nested else ""
        out.append(f"scopes: {name:32s} {line['fwd'] / per:8.2f} "
                   f"{line['remat'] / per:8.2f} {line['bwd'] / per:8.2f} "
                   f"{whole / per:8.2f} {100 * whole / busy_ns:7.2f} {held}")
    out.append(f"scopes: the lines sum to {total / per:.2f} ms, "
               f"{100 * total / busy_ns:.2f} % of the busy time; instruction "
               f"names the table did not hold: {100 * unknown / busy_ns:.3f} "
               "% of it")
    out.append(f"scopes: the {groups} largest instruction groups in no "
               "scope (ms a run, events a run, name, op_name, one of them)")
    for (base, op_name), (ns, count, text) in sorted(
            loose.items(), key=lambda kv: -kv[1][0])[:groups]:
        out.append(f"scopes: {ns / per:8.2f} {count / len(runs):7.1f} "
                   f"{base} [{op_name or 'no op_name'}] {text[:140]}")
    return out


def scope_ms_per_run(r, module, scopes=None, unscoped=False,
                     exclude_contains=(), collectives=True, share=False):
    """Device time, in ms per whole run of the program ``module`` on the
    first device, of the instructions that lie in any of ``scopes``
    (nested ones included), or (``unscoped``) in no scope and no kernel row
    of the program's, an instruction the table does not hold among them.
    ``exclude_contains``: instruction names to leave out;
    ``collectives=False``: collectives are left out; ``share``: as a
    percentage of the runs' busy time. None when the program has no scope
    table, when no run is whole, or when none of ``scopes`` is in the
    program."""
    import horovod_tpu as hvd
    if r.win is None or not hasattr(hvd.tracing, "scope_table"):
        return None
    kept = vars(r).setdefault("scopes_joined", {})      # a run's, by module
    if module not in kept:
        table = hvd.tracing.scope_table(module)
        cut = table and join(r.win, module, table)
        if not cut:
            return None
        kept[module] = (table, *cut)
        print("\n".join(table_lines(module, *cut)), flush=True)
    table, runs, busy_ns, events = kept[module]
    wanted = set(scopes or ())
    if wanted and not any(wanted & set(row.scopes)
                          for row in table.values()):
        return None
    spent = 0
    for row, text, ns in events:
        if unscoped:
            hit = row is None or row.layer is None
        else:
            hit = row is not None and bool(wanted & set(row.scopes))
        if not hit or (not collectives and xplane.is_collective(text)):
            continue
        name = xplane.instruction_name(text)
        if any(part in name for part in exclude_contains):
            continue
        spent += ns
    return 100.0 * spent / busy_ns if share else spent / len(runs) / 1e6
