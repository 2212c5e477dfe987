#!/usr/bin/env python
"""Regression sentinel over the self-measured bench log (ROADMAP
"regression sentinel": fail the build when a tracked proxy metric drops).

The log (``--log``; default ``BENCH_SELF.jsonl`` at the repo root, absent
until a tool's ``--out`` creates it) is append-only — every CPU-proxy bench
run (serve/topology/mp sweeps) adds one JSON line with ``"proxy": true``
plus the settings it ran at. The sentinel compares
each identity's NEWEST line against the LATEST PRIOR line at EQUAL
settings and exits 2 when the value degraded more than the threshold
(10% by default — proxy numbers on shared CI hardware are noisy;
anything past that is a code smell, not scheduler jitter).

"Equal settings" is structural, not positional: the identity key is
(model, metric, variant, unit) plus every settings field the line
carries from a fixed whitelist — a serve line at rate=50 never gates a
rate=25 line, and a swing topology sweep never gates a ring one.
Non-proxy lines (real-TPU numbers) are exempt: chip numbers are judged
from the driver's ledger, not here.

Exit codes: 0 = no comparable pair degraded (including "nothing to
compare"), 2 = at least one regression. ``--threshold`` overrides the
10%. Wired as ``make bench-sentinel``; the comparison logic is
unit-tested on canned lines in ``tests/test_bench_sentinel.py``.
"""

import argparse
import json
import os
import sys
from typing import Any, Dict, List, Tuple

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_LOG = os.path.join(REPO, "BENCH_SELF.jsonl")

# Settings fields that must match for two lines to be comparable —
# anything here that differs means a different experiment, not a
# regression. Result-ish numeric fields (value, *_tflops, hfu, ...)
# deliberately absent.
SETTINGS_KEYS = (
    "transport", "slots", "max_len", "block_size", "prefill_chunk",
    "kv_quant", "arrival_rate_hz", "requests", "rate",
    "allreduce_alg", "wire", "topology", "mesh", "overlap_chunks",
    "payload_mb", "world", "batch", "seq_len", "steps",
    "prefix_overlap", "prefix_cache", "spec_k", "request_trace",
    "slo_ttft_p99_ms", "slo_error_rate",
    "serve_role", "kv_wire", "affinity",
    "config_epoch",
)


def _identity(rec: Dict[str, Any]) -> Tuple:
    ident: List[Tuple[str, Any]] = [
        ("model", rec.get("model")), ("metric", rec.get("metric")),
        ("variant", rec.get("variant")), ("unit", rec.get("unit"))]
    for k in SETTINGS_KEYS:
        if k in rec:
            ident.append((k, rec[k]))
    return tuple(ident)


def check_lines(lines, threshold: float = 0.10):
    """Compare each identity's newest proxy line vs its latest prior one.

    ``lines`` is an iterable of raw JSONL strings in log order (oldest
    first — the file is append-only). Returns ``(regressions,
    compared)``: ``regressions`` is a list of dicts (identity, prior,
    latest, drop fraction), ``compared`` the number of identities that
    had a comparable pair. Unparseable lines, non-proxy lines, and
    null/zero values are skipped — the sentinel gates code, it never
    crashes on a hand-edited log."""
    by_ident: Dict[Tuple, List[Dict[str, Any]]] = {}
    for raw in lines:
        raw = (raw or "").strip()
        if not raw.startswith("{"):
            continue
        try:
            rec = json.loads(raw)
        except ValueError:
            continue
        if not rec.get("proxy"):
            continue
        value = rec.get("value")
        if not isinstance(value, (int, float)) or value <= 0:
            continue
        by_ident.setdefault(_identity(rec), []).append(rec)

    regressions = []
    compared = 0
    for ident, recs in by_ident.items():
        if len(recs) < 2:
            continue
        compared += 1
        prior, latest = recs[-2], recs[-1]
        drop = (prior["value"] - latest["value"]) / prior["value"]
        if drop > threshold:
            regressions.append({
                "identity": dict(ident),
                "prior": {"ts": prior.get("ts"), "git": prior.get("git"),
                          "value": prior["value"]},
                "latest": {"ts": latest.get("ts"), "git": latest.get("git"),
                           "value": latest["value"]},
                "drop": drop,
            })
    return regressions, compared


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--log", default=DEFAULT_LOG,
                   help="path to BENCH_SELF.jsonl")
    p.add_argument("--threshold", type=float, default=0.10,
                   help="max tolerated fractional drop (default 0.10)")
    args = p.parse_args(argv)
    try:
        with open(args.log) as f:
            lines = f.readlines()
    except OSError as e:
        print(f"bench-sentinel: cannot read {args.log}: {e}",
              file=sys.stderr)
        return 0              # no log yet is not a regression
    regressions, compared = check_lines(lines, threshold=args.threshold)
    if not regressions:
        print(f"bench-sentinel OK: {compared} tracked metric(s), none "
              f"degraded past {args.threshold:.0%}")
        return 0
    print(f"bench-sentinel: {len(regressions)} regression(s) past "
          f"{args.threshold:.0%} across {compared} tracked metric(s)",
          file=sys.stderr)
    for r in regressions:
        ident = r["identity"]
        label = ident.get("metric") or ident.get("model")
        if ident.get("variant"):
            label = f"{label} [{ident['variant']}]"
        print(f"  {label}: {r['prior']['value']} "
              f"(git {r['prior']['git']}) -> {r['latest']['value']} "
              f"(git {r['latest']['git']}), -{r['drop']:.1%}",
              file=sys.stderr)
    return 2


if __name__ == "__main__":
    sys.exit(main())
