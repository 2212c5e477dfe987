"""The reader over the program's scope table (``readers/scopes.py``), on a
synthetic window whose events use instruction names as the v5e's compiler
wrote them, against a table of rows shaped as the program's (it reads
attributes, so the tests pass over a program that has no table yet), and
the eight metrics that are data for it.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_scope_reader.py -q

Not part of the repo's tier-1 tests: the benchmark checks itself
(``tests/test_tracing_spans.py`` holds the same reader to a real table).
"""

import collections
import inspect
import json
import os
import sys
from types import SimpleNamespace

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, ROOT)

import pytest  # noqa: E402

import horovod_tpu as hvd  # noqa: E402
from readers import scopes  # noqa: E402

ScopeRow = collections.namedtuple(
    "ScopeRow", "scopes layer direction container kernel op_name")

EXPERTS = "expert layer (ops/moe)"
KERNELS = "kernels (ops/flash_attention)"
MODEL = "models (models/lfm2, remat)"
STEP = "jit_train_step(7788)"
METRICS = ["unscoped_time_share.train", "grad_sync_local_ms.train",
           "flash_layout_ms.train", "moe_experts_outside_products_ms.train",
           "loss_head_ms.train", "loss_head_ms.train_glm4",
           "mla_expand_ms.train", "shortconv_ms.train"]


def _row(scopes=(), layer=None, direction="fwd", container=False,
         kernel=None, op_name=""):
    return ScopeRow(tuple(scopes), layer, direction, container, kernel,
                    op_name)


# names as the chip's compiler wrote them for L1's step (an AOT compile for
# a v5e): a loop over windows, the grouped kernel and a gather inside it, a
# copy of the kernels' layout, an all-reduce of the sync, a copy of nobody's
TABLE = {
    "while.12": _row(["moe/experts"], EXPERTS, container=True,
                     op_name="jit(train_step)/jvp(LFM2)/h1/moe/moe/experts/"
                             "while"),
    "ragged-dot-none.3": _row(layer=EXPERTS, kernel="ragged-dot"),
    "gather_fusion.7": _row(["moe/experts"], EXPERTS,
                            op_name="jit(train_step)/jvp(LFM2)/h1/moe/moe/"
                                    "experts/while/body/gather"),
    "scatter_fusion.2": _row(["moe/experts"], EXPERTS, "bwd"),
    "copy.159": _row(["lfm2/attn", "flash/layout"], KERNELS, "remat"),
    "flash_fwd.2": _row(["lfm2/attn", "flash_attention"], KERNELS,
                        kernel="flash_fwd"),
    "fusion.40": _row(["lfm2/shortconv"], MODEL, "bwd"),
    "all-reduce.1": _row(["hvd/value_and_grad/sync"], "trainer"),
    "divide_fusion.9": _row(["hvd/value_and_grad/sync"], "trainer"),
    "concatenate.4": _row(["hvd/value_and_grad/sync", "hvd/fusion/pack"],
                          "trainer"),
    "copy-start.88": _row(),
}


def _event(name, start, ns, opcode="fusion"):
    return (f"%{name} = f32[8,128]{{1,0:T(8,128)}} {opcode}(f32[8,128] "
            f"%p.1)", start, ns)


def _reader_context(table=TABLE):
    ops = []
    for run in (1000, 3000):
        ops += [_event("while.12", run, 500, "while"),
                _event("gather_fusion.7", run, 100),
                _event("ragged-dot-none.3", run + 100, 300, "custom-call"),
                _event("scatter_fusion.2", run + 500, 200),
                _event("copy.159", run + 700, 50, "copy"),
                _event("flash_fwd.2", run + 750, 250, "custom-call"),
                _event("fusion.40", run + 1000, 150),
                _event("all-reduce.1", run + 1150, 400, "all-reduce"),
                _event("divide_fusion.9", run + 1550, 30),
                _event("concatenate.4", run + 1580, 20, "concatenate"),
                _event("copy-start.88", run + 1600, 60, "copy-start"),
                _event("fusion.31337", run + 1700, 40)]
    win = {"modules": [(STEP, 1000, 2000), (STEP, 3000, 2000)],
           "ops": ops, "asyncs": []}
    return SimpleNamespace(win=win)


@pytest.fixture()
def table(monkeypatch):
    monkeypatch.setattr(hvd.tracing, "scope_table",
                        lambda program: TABLE if program == "train_step"
                        else None, raising=False)


# a run is busy 1,700 ns: the loop's 500, then 200 + 50 + 250 + 150 + 400 +
# 30 + 20 + 60 back to back and 40 after a gap; its lines hold 1,600 (the
# loop's body is 400 of its 500)
BUSY = 500 + 200 + 50 + 250 + 150 + 400 + 30 + 20 + 60 + 40


@pytest.mark.parametrize("args,want", [
    (dict(scopes=["moe/experts"]), (100 + 200) * 1e-6),
    (dict(scopes=["moe/experts"], exclude_contains=["ragged-dot"]),
     (100 + 200) * 1e-6),
    (dict(scopes=["flash/layout"]), 50e-6),
    (dict(scopes=["lfm2/attn"]), (50 + 250) * 1e-6),
    (dict(scopes=["lfm2/shortconv"]), 150e-6),
    (dict(scopes=["hvd/fusion/pack", "hvd/fusion/unpack",
                  "hvd/value_and_grad/sync", "hvd/optimizer/sync"],
          collectives=False), (30 + 20) * 1e-6),
    (dict(scopes=["hvd/value_and_grad/sync"]), (400 + 30 + 20) * 1e-6),
    (dict(unscoped=True), (60 + 40) * 1e-6),
    (dict(unscoped=True, share=True), 100.0 * (60 + 40) / BUSY),
    (dict(scopes=["glm4/mla_up"]), None),
], ids=["the-loop-skipped-its-body-counted", "products-left-out",
        "layout", "all-a-scope-holds", "one-scope", "sync-without-collectives",
        "sync-with-them", "unscoped-and-unknown", "share-of-busy",
        "a-scope-the-program-lacks"])
def test_sums_by_hand(table, capsys, args, want):
    got = scopes.scope_ms_per_run(_reader_context(), "train_step", **args)
    assert got == (want if want is None else pytest.approx(want))
    printed = capsys.readouterr().out.splitlines()
    assert printed[0].startswith("scopes: train_step, 2 whole runs")
    # the kernel in no scope has a line of its own, under its layer's name
    assert any("(kernel) ragged-dot" in line for line in printed)
    assert any(line.startswith("scopes: no scope of ours") for line in printed)
    assert any(line.startswith("scopes: not in the table") for line in printed)
    total = 100.0 * (BUSY - 500 + 400) / BUSY
    assert any(f"{total:.2f} % of the busy time" in line
               and f"did not hold: {100.0 * 40 / BUSY:.3f} %" in line
               for line in printed)


def test_the_products_have_the_expert_layer_without_a_scope(table):
    """``ragged-dot`` is no scope: a sum over ``moe/experts`` never held the
    grouped products, whose custom calls carry no metadata, and leaving
    them out by name changes nothing; they are not unscoped either."""
    r = _reader_context()
    held = scopes.scope_ms_per_run(r, "train_step", scopes=["moe/experts"])
    out = scopes.scope_ms_per_run(r, "train_step", scopes=["moe/experts"],
                                  exclude_contains=["ragged-dot"])
    assert held == out == pytest.approx(300e-6)
    loose = scopes.scope_ms_per_run(r, "train_step", unscoped=True)
    assert loose == pytest.approx(100e-6)       # the copy and the unknown


@pytest.mark.parametrize("how", ["no-table", "no-function", "no-trace",
                                 "no-whole-run"])
def test_reads_nothing_and_does_not_raise(monkeypatch, how):
    r = _reader_context()
    if how == "no-table":
        monkeypatch.setattr(hvd.tracing, "scope_table", lambda program: None,
                            raising=False)
    elif how == "no-function":      # the parent commit
        monkeypatch.delattr(hvd.tracing, "scope_table", raising=False)
    elif how == "no-trace":
        monkeypatch.setattr(hvd.tracing, "scope_table", lambda program: TABLE,
                            raising=False)
        r = SimpleNamespace(win=None)
    else:
        monkeypatch.setattr(hvd.tracing, "scope_table", lambda program: TABLE,
                            raising=False)
        r.win["modules"] = []
    assert scopes.scope_ms_per_run(r, "train_step", unscoped=True,
                                   share=True) is None


@pytest.mark.parametrize("name", METRICS)
def test_each_metric_names_its_entry(name):
    """The file and the ``BENCHMARK.json`` entry say the same, the entry is
    appended after the accepted ones, and the file's ``args`` are arguments
    of the reader."""
    with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
        spec = json.load(f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entries = json.load(f)["per_layer"]
    entry = [m for m in entries if m["name"] == name]
    assert len(entry) == 1 and spec["name"] == name
    for key in ("unit", "layer", "moves", "source", "better", "workloads"):
        assert spec[key] == entry[0][key], key
    assert [m["name"] for m in entries[-len(METRICS):]] == METRICS
    assert spec["reader"] == "scopes:scope_ms_per_run"
    accepted = inspect.signature(scopes.scope_ms_per_run).parameters
    assert set(spec["args"]) <= set(accepted) and "module" in spec["args"]
    assert spec["what"]
