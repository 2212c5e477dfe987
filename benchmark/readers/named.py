"""Readers over what the program names itself (``horovod_tpu/tracing.py``,
its table ``NAMES``): the device time of operations by instruction name, and
the series of the program's own metrics registry. The strings and labels
are each metric's ``args``, not code. A program that names nothing (an
older commit) reads as None, and the metric is left out of the line."""

from reduce import xplane


def op_ms_per_run(r, module, contains):
    """Device time, in ms per whole run of the program ``module`` on the
    first device, of the operations whose instruction name holds
    ``contains`` (``%flash_fwd.7 = ... custom-call(...)`` holds
    ``flash_fwd``). None when no such operation ran."""
    if r.win is None:
        return None
    cut = xplane.whole_runs(r.win, module)
    if cut is None:
        return None
    runs, ops, _ = cut
    hit = [d for n, _, d in ops if contains in xplane.instruction_name(n)]
    if not hit:
        return None
    return sum(hit) / len(runs) / 1e6


def _matches(labels, want):
    """Every wanted label is there and holds the wanted string, or one of
    the wanted strings."""
    for key, value in want.items():
        have = labels.get(key)
        options = value if isinstance(value, list) else [value]
        if have is None or not any(o in have for o in options):
            return False
    return True


def _total(snapshot, selectors):
    """Sum over the selected series: a counter's or gauge's value, a
    histogram's sum. None when a selector matched no series: a sum that
    lacks a part (an older program has ``init_seconds`` and no
    ``import_seconds``) is not the metric."""
    total = 0.0
    for sel in selectors:
        found = [series[field]
                 for kind, field in (("counters", "value"),
                                     ("gauges", "value"),
                                     ("histograms", "sum"))
                 for series in snapshot.get(kind, {}).get(sel["name"], ())
                 if _matches(series["labels"], sel.get("labels", {}))]
        if not found:
            return None
        total += sum(found)
    return total


def series_total(r, series, per=None, scale=1.0):
    """``scale`` x the sum of the ``series`` of ``hvd.metrics.snapshot()``
    (same process), over the sum of the ``per`` series when given. A
    selector is ``{"name": ..., "labels": {label: substring or list of
    substrings}}``. None when a selector matched nothing or ``per`` sums
    to 0."""
    import horovod_tpu as hvd
    snapshot = hvd.metrics.snapshot()
    top = _total(snapshot, series)
    if top is None:
        return None
    if per is None:
        return scale * top
    bottom = _total(snapshot, per)
    return scale * top / bottom if bottom else None
