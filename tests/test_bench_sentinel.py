"""bench-sentinel comparison logic on canned bench-log lines
(ROADMAP "regression sentinel"; ``make bench-sentinel``)."""

import json
import os
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def sentinel():
    sys.path.insert(0, os.path.join(_REPO, "tools"))
    import bench_sentinel as bs
    yield bs
    sys.path.remove(os.path.join(_REPO, "tools"))


def _line(value, *, model="gpt2-tiny", metric="serve_tokens_per_sec",
          variant="serve rate=25", proxy=True, git="abc1234", **settings):
    rec = {"ts": "2026-08-05T00:00:00+00:00", "git": git, "model": model,
           "metric": metric, "variant": variant, "value": value,
           "unit": "tokens/sec", "vs_baseline": None}
    if proxy:
        rec["proxy"] = True
    rec.update(settings)
    return json.dumps(rec)


def test_regression_past_threshold_is_flagged(sentinel):
    lines = [_line(400.0, git="old1111"), _line(350.0, git="new2222")]
    regs, compared = sentinel.check_lines(lines, threshold=0.10)
    assert compared == 1
    assert len(regs) == 1
    assert regs[0]["drop"] == pytest.approx(0.125)
    assert regs[0]["prior"]["git"] == "old1111"
    assert regs[0]["latest"]["git"] == "new2222"


def test_drop_within_threshold_passes(sentinel):
    lines = [_line(400.0), _line(365.0)]          # -8.75%
    regs, compared = sentinel.check_lines(lines, threshold=0.10)
    assert compared == 1 and regs == []


def test_improvement_passes(sentinel):
    regs, compared = sentinel.check_lines([_line(400.0), _line(500.0)])
    assert compared == 1 and regs == []


def test_latest_vs_latest_prior_not_oldest(sentinel):
    # The sentinel gates the NEWEST line against the line right before
    # it: an old bad number must not forgive a fresh regression, and a
    # recovered metric must not keep failing on ancient history.
    lines = [_line(500.0), _line(300.0), _line(290.0)]   # newest -3.3%
    regs, _ = sentinel.check_lines(lines)
    assert regs == []
    lines = [_line(300.0), _line(500.0), _line(400.0)]   # newest -20%
    regs, _ = sentinel.check_lines(lines)
    assert len(regs) == 1 and regs[0]["prior"]["value"] == 500.0


def test_different_settings_are_not_comparable(sentinel):
    # Same metric at different slots counts: separate experiments.
    lines = [_line(400.0, slots=4), _line(200.0, slots=8)]
    regs, compared = sentinel.check_lines(lines)
    assert compared == 0 and regs == []
    # ... and per-variant histories gate independently.
    lines = [_line(400.0, variant="transport=spool"),
             _line(400.0, variant="transport=socket"),
             _line(100.0, variant="transport=socket")]
    regs, compared = sentinel.check_lines(lines)
    assert compared == 1 and len(regs) == 1
    assert regs[0]["identity"]["variant"] == "transport=socket"


def test_equal_settings_are_comparable(sentinel):
    lines = [_line(400.0, slots=8, transport="socket"),
             _line(200.0, slots=8, transport="socket")]
    regs, compared = sentinel.check_lines(lines)
    assert compared == 1 and len(regs) == 1


def test_non_proxy_lines_are_exempt(sentinel):
    # Real-TPU lines vary with relay availability, not code: never gate.
    lines = [_line(400.0, proxy=False), _line(100.0, proxy=False)]
    regs, compared = sentinel.check_lines(lines)
    assert compared == 0 and regs == []


def test_garbage_and_null_values_are_skipped(sentinel):
    lines = ["not json", "", "# comment", _line(None), _line(0.0),
             _line(400.0), _line(395.0)]
    regs, compared = sentinel.check_lines(lines)
    assert compared == 1 and regs == []


def test_single_line_has_nothing_to_compare(sentinel):
    regs, compared = sentinel.check_lines([_line(400.0)])
    assert compared == 0 and regs == []


def test_main_exit_codes(sentinel, tmp_path, capsys):
    log = tmp_path / "BENCH_SELF.jsonl"
    log.write_text(_line(400.0) + "\n" + _line(100.0) + "\n")
    assert sentinel.main(["--log", str(log)]) == 2
    assert "-75.0%" in capsys.readouterr().err
    log.write_text(_line(400.0) + "\n" + _line(405.0) + "\n")
    assert sentinel.main(["--log", str(log)]) == 0
    assert sentinel.main(["--log", str(tmp_path / "missing.jsonl")]) == 0
