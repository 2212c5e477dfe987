"""``run.py`` end to end on the CPU for the fifth family, ``smallthinker``,
at a tiny size: a throw-away cell whose configuration keeps the published
kinds of layer (one period: a global layer without positions and three
window layers with RoPE; 8 experts of which 2 are held, top-2 by the softmax
of the block's input, ReGLU, a vocabulary slice) and whose traffic is
``train-fixed-2x16384`` cut to 2 rows of 32 tokens under a window of 8. New
files and entries only, as ``test_rehearse.py`` does it. Every entry of
``BENCHMARK.json`` is found **by name**: a later PR appends, and nothing
here holds an entry to be the last. Not part of tier-1.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_rehearse_smallthinker.py -q
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

from test_rehearse import BENCH, CPU, ROOT, _dump, _load, _run

NAME = "tiny-smallthinker"
REAL = "smallthinker21b-train-dp1"
CONFIG = "smallthinker-21b-a3b-train"
TRAFFIC = "train-fixed-2x16384"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
OWN = ["mfu.train_smallthinker", "swa_flash_roofline.train",
       "swa_flash_time_share.train", "window_tiles_visited_share.train",
       "window_attn_ms.train"]
SHARED = ["collective_ms.train", "collective_exposed_ms.train",
          "device_idle_share.train", "unscoped_time_share.train",
          "grad_sync_local_ms.train", "flash_layout_ms.train",
          "flash_bwd_kernels.train", "moe_expert_time_share.train",
          "moe_local_assignments.train", "moe_rows_filled_share.train",
          "moe_experts_outside_products_ms.train",
          "causal_tiles_visited_share.train"]
sys.path.insert(0, BENCH)


def _named(entries, name):
    hit = [e for e in entries if e["name"] == name]
    assert len(hit) == 1, name
    return hit[0]


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    import controls_smallthinker
    tmp = str(tmp_path_factory.mktemp("bench_smallthinker"))
    shutil.copytree(BENCH, os.path.join(tmp, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = _load(ROOT, "BENCHMARK.json")
    config = _load(BENCH, "configs", CONFIG + ".json")
    config.update(controls_smallthinker.TINY)
    config["deployment"].update(router_width=8, experts_first=2)
    config["run"].update(compute_dtype="float32")
    traffic = _load(BENCH, "traffic", TRAFFIC + ".json")
    traffic.update(sequences_per_chip=2, seq_len=32, loss_rel_tol=1e-4,
                   grad_norm_rel_tol=1e-3)
    bench["configs"].append({
        "name": NAME, "source": "test", "reduced": [], "why": "test",
        "file": f"benchmark/configs/{NAME}.json"})
    _dump(config, tmp, "benchmark", "configs", NAME + ".json")
    _dump(traffic, tmp, "benchmark", "traffic", NAME + ".json")
    bench["workloads"].append({"name": NAME, "config": NAME, "traffic": NAME,
                               "chips": 1, "why": "test"})
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if REAL in metric.get("workloads", ()):
            metric["workloads"].append(NAME)
    _dump(bench, tmp, "BENCHMARK.json")
    return tmp


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_window_and_global_attention_cell(checkout, trace):
    done = _run(checkout, NAME, trace, CPU)
    assert done.returncode == 0, done.stdout[-3000:] + done.stderr[-3000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0 and last["metrics"] == {}
    assert "programs compiled in the window: 0" in done.stdout
    assert "[smallthinker] routing of the check batch" in done.stdout
    assert "weights from the fixed seed 37" in done.stdout
    if trace:
        # the readers over the program's own gauges find them; the device
        # readers find no device on a CPU and leave their metrics out
        values = json.loads(done.stdout.split(
            "rehearsal values (CPU, not metrics): ")[1].splitlines()[0])
        assert values["moe_local_assignments.train"]["value"] > 0
        window = values["window_tiles_visited_share.train"]["value"]
        causal = values["causal_tiles_visited_share.train"]["value"]
        assert 0 < window <= causal <= 100       # one tile at T 32
        assert values["flash_bwd_kernels.train"]["value"] in (1, 2)
        assert values["compile_s"]["value"] > 0      # no list: every cell
        for name in ("swa_flash_roofline.train", "mfu.train_smallthinker",
                     "window_attn_ms.train", "moe_bias_moved_share.train",
                     "flash_dq_ms.train", "mfu.train_glm4"):
            assert name not in values, name


def test_a_failed_check_reaches_the_driver_as_nan(monkeypatch):
    """The routing's faults (``test_rehearse_lfm2.py`` holds the judge
    itself to its three) and the attention's reach the driver through this
    family as NaN in the reference's place."""
    import numpy as np
    from families import smallthinker as fam
    config = {"check": {"router_differ_share_max": 0.0,
                        "routing_differ_share_max": 0.0,
                        "attention_differ_max": 0.1}}
    monkeypatch.setattr(fam, "shapes", lambda config: {
        "experts_first": 0, "top_k": 2, "norm_topk": True})
    monkeypatch.setattr(fam, "program_config", lambda config: None)
    monkeypatch.setattr(fam, "system_tree", lambda ref: None)
    monkeypatch.setattr(fam, "routers_of", lambda ref: None)
    monkeypatch.setattr(fam.smallthinker_ref, "loss_and_grad_norm",
                        lambda *a, **k: (1.5, 2.5))
    # two layers, one row of two positions
    mine = np.array([[[[0, 1], [0, 1]]], [[[0, 1], [2, 3]]]])
    sizes = np.array([[2, 2, 0, 0], [1, 1, 1, 1]])
    attn = np.ones((2, 1, 2, 4), np.float32)
    monkeypatch.setattr(fam, "routing_of",
                        lambda *a: (sizes, mine, None, attn))
    monkeypatch.setattr(fam.smallthinker_ref, "router_choices",
                        lambda *a, **k: mine)
    theirs = mine.copy()
    theirs[0, 0, 1] = [0, 2]        # the first layer's second position
    monkeypatch.setattr(fam, "reference_look",
                        lambda *a, **k: (theirs, attn))
    tokens = np.zeros((1, 2), np.int32)
    got = fam._checked({}, tokens, micro=1, config=config)
    assert np.isnan(got[0]) and np.isnan(got[1])
    config["check"]["routing_differ_share_max"] = 0.5
    assert fam._checked({}, tokens, micro=1, config=config) == (1.5, 2.5)
    # an attention output a fifth of its norm away, in one layer
    off = attn.copy()
    off[1] *= 1.2
    monkeypatch.setattr(fam, "reference_look",
                        lambda *a, **k: (theirs, off))
    got = fam._checked({}, tokens, micro=1, config=config)
    assert np.isnan(got[0]) and np.isnan(got[1])
    faults, differ = fam.attention_faults(attn, off, config["check"])
    assert len(faults) == 1 and differ[0] == 0
    assert differ[1] == pytest.approx(0.2 / 1.2)


def test_the_seven_controls_run_through_the_same_comparison():
    """``controls_smallthinker.py`` at its tiny size: the system passes; a
    dropped row, the three wrong layouts, the router after attention and
    silu for relu each fail by a limit aimed at it. (Whether bfloat16
    logits flip a choice among 128 is the seed's luck; at the
    configuration's size they flip hundreds, PERF.md.)"""
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "controls_smallthinker.py"),
         "7"], env=dict(os.environ, **CPU), capture_output=True, text=True,
        cwd=ROOT, timeout=900)
    lines = [l for l in done.stdout.splitlines() if l.startswith("7 ")]
    assert len(lines) == 8, done.stdout[-2000:] + done.stderr[-2000:]
    assert lines[0].startswith("7 system:") and lines[0].endswith("passes")
    assert "one_assignment_dropped" in lines[1] and "FAILS" in lines[1]
    assert "without a row" in lines[1]
    assert "router_logits_bf16" in lines[2]
    for line, name in zip(lines[3:], (
            "window_layers_run_causal", "rope_on_the_global_layer",
            "rope_left_off_a_window_layer",
            "router_fed_the_normed_stream_after_attention",
            "silu_for_relu")):
        assert name in line and "FAILS" in line, line
    for line in lines[3:6]:
        assert "attention outputs that differ" in line
    assert "choices that differ from the reference's" in lines[6]


def test_the_real_cell_is_entered_as_the_issue_names_it():
    from families import smallthinker as fam
    bench = _load(ROOT, "BENCHMARK.json")
    cell = _named(bench["workloads"], REAL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, TRAFFIC, 1)
    config = _load(BENCH, "configs", CONFIG + ".json")
    entry = _named(bench["configs"], CONFIG)
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert sorted(entry["reduced"]) == sorted(config["reduced"]) == sorted([
        "num_hidden_layers", "sliding_window_layout", "rope_layout",
        "moe_num_primary_experts", "vocab_size"])
    assert entry["source"] == config["source"]
    published = dict(config, **{k: config["published"][k]
                                for k in config["reduced"]})
    # every published number stands but those that are cut: against the
    # catalog's row, on a machine that has the catalog
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = [r for r in map(json.loads, f)
                   if r["name"] == "SmallThinker-21BA3B-Instruct"][0]
        assert config["source"] == row["source_url"]
        for k, v in row["config"].items():
            assert (config[k] == v) == (k not in config["reduced"]), k
            if not isinstance(v, list):
                assert published[k] == v, k
        assert row["config"]["sliding_window_layout"] == \
            config["sliding_window_layout"] * 13
        assert row["config"]["rope_layout"] == config["rope_layout"] * 13
    # the floors of a cut: a whole period, 8 experts, an eighth of the rows
    assert config["num_hidden_layers"] == 4
    assert config["sliding_window_layout"] == config["rope_layout"] == [
        0, 1, 1, 1]
    assert config["moe_num_primary_experts"] >= 8
    assert config["vocab_size"] * 8 >= published["vocab_size"]
    assert config["deployment"]["router_width"] == 64
    cfg = fam.program_config(config)
    assert (cfg.num_layers, cfg.experts_held, cfg.experts_total, cfg.top_k,
            cfg.vocab_size, cfg.sliding_window, cfg.num_heads,
            cfg.num_kv_heads, cfg.head_dim, cfg.d_model, cfg.d_expert) == (
                4, (0, 8), 64, 6, 18992, 4096, 28, 4, 128, 2560, 768)
    traffic = _load(BENCH, "traffic", cell["traffic"] + ".json")
    assert traffic["driver"] == "train_steps"
    assert (traffic["sequences_per_chip"], traffic["seq_len"]) == (2, 16384)
    assert traffic["seq_len"] == config["max_position_embeddings"]
    # the cell's metrics have their files, and the files say what the
    # entries say; the shared ones list the cell
    own = [m["name"] for m in bench["per_layer"]
           if m.get("workloads") == [REAL]]
    assert sorted(own) == sorted(OWN)
    for name in OWN:
        metric = _named(bench["per_layer"], name)
        spec = _load(BENCH, "layer_metrics", name + ".json")
        for k, v in metric.items():
            assert spec[k] == v, (name, k)
    for name in SHARED:
        assert REAL in _named(bench["per_layer"], name)["workloads"], name
    assert REAL in _named(bench["end_to_end"],
                          "train_tokens_per_s_chip")["workloads"]
    for name in ("flash_dq_ms.train", "moe_bias_moved_share.train"):
        assert REAL not in _named(bench["per_layer"], name)["workloads"]


def test_a_configuration_the_program_does_not_build_is_refused():
    from families import smallthinker as fam
    config = _load(BENCH, "configs", CONFIG + ".json")
    for other in (dict(tie_word_embeddings=True),
                  dict(moe_primary_router_apply_softmax=False),
                  dict(rope_scaling={"type": "yarn"})):
        with pytest.raises(ValueError, match="asks for something else"):
            fam.program_config(dict(config, **other))


def test_operations_a_token_requires():
    """``flops_smallthinker.py`` against ISSUE 37's arithmetic at the
    published widths."""
    import flops_smallthinker as flops
    config = _load(BENCH, "configs", CONFIG + ".json")
    attention = 2 * 2560 * 3584 + 2 * 2560 * 512
    layer = attention + 2560 * 64 + 6 * (8 / 64) * 3 * 2560 * 768
    assert 2 * layer == pytest.approx(51.1e6, rel=1e-3)
    met = 4 * layer + 18992 * 2560
    assert flops.matmul_params(config) == pytest.approx(met)
    full, band = flops.pairs_per_token(config, 16384)
    assert (full, band) == (8192, 3584)
    forward = flops.attention_fwd_flops_per_token(config, 16384)
    assert 4 * 3584 * full == pytest.approx(117.4e6, rel=1e-3)
    assert 3 * 4 * 3584 * band == pytest.approx(154.1e6, rel=1e-3)
    assert forward == 4 * 3584 * (full + 3 * band)
    per_token = flops.train_flops_per_token(config, 16384)
    assert per_token == pytest.approx(6 * met + 3 * forward)
    assert per_token / 3 == pytest.approx(573.3e6, rel=1e-3)
    assert per_token * 32768 == pytest.approx(56.4e12, rel=2e-3)
    assert forward / (per_token / 3) == pytest.approx(0.474, abs=0.002)
    # the window layers run causal: 1.35 x the step, 2.29 x the layer
    causal = 2 * met + 4 * 3584 * 4 * full
    assert causal / (per_token / 3) == pytest.approx(1.35, abs=0.005)
    assert full / band == pytest.approx(2.29, abs=0.005)
    # a row no longer than the window sees the triangle in every layer
    assert flops.pairs_per_token(config, 2048) == (1024, 1024)
    assert flops.flash_train_flops_per_token(config, 16384) == \
        pytest.approx(3.5 * forward)
    # grouped-query: 4 key/value heads of 128 serve 28
    assert flops.flash_train_bytes_per_token(config, 16384) == \
        4 * ((2 * 3584 + 2 * 512) + (3 * 3584 + 2 * 512)
             + (3584 + 2 * 512)) * 2
