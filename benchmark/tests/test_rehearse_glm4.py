"""``run.py`` end to end on the CPU for the fourth family, ``glm4_moe_lite``,
at a tiny size: a throw-away cell whose configuration keeps the published
kinds of layer (a dense block, two blocks with routed experts and a shared
one, the multi-token-prediction module; 8 experts of which 2 are held, top-4
by score + bias, a vocabulary slice) and whose traffic is
``train-fixed-2x8192`` cut to 2 rows of 32 tokens. New files and entries
only, as ``test_rehearse.py`` does it. Not part of tier-1.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_rehearse_glm4.py -q
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

from test_rehearse import BENCH, CPU, ROOT, _dump, _load, _run

NAME = "tiny-glm4"
REAL = "glm47f-train-dp1"
CONFIG = "glm-4.7-flash-train"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
sys.path.insert(0, BENCH)


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    import controls_glm4
    tmp = str(tmp_path_factory.mktemp("bench_glm4"))
    shutil.copytree(BENCH, os.path.join(tmp, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = _load(ROOT, "BENCHMARK.json")
    config = _load(BENCH, "configs", CONFIG + ".json")
    config.update(controls_glm4.TINY)
    config["deployment"].update(router_width=8, experts_first=2)
    config["run"].update(compute_dtype="float32")
    traffic = _load(BENCH, "traffic", "train-fixed-2x8192.json")
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
def test_rehearsal_of_the_latent_attention_cell(checkout, trace):
    done = _run(checkout, NAME, trace, CPU)
    assert done.returncode == 0, done.stdout[-3000:] + done.stderr[-3000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0 and last["metrics"] == {}
    assert "programs compiled in the window: 0" in done.stdout
    assert "[glm4_moe_lite] routing of the check batch" in done.stdout
    assert "weights and selection bias from the fixed seed 33" in done.stdout
    if trace:
        # the readers over the program's own gauges find them; the device
        # readers find no device on a CPU and leave their metrics out
        values = json.loads(done.stdout.split(
            "rehearsal values (CPU, not metrics): ")[1].splitlines()[0])
        assert 0 < values["moe_bias_moved_share.train"]["value"] < 100
        assert values["moe_local_assignments.train"]["value"] > 0
        assert 0 < values["causal_tiles_visited_share.train"]["value"] <= 100
        # 2 x 32 positions x 4 attention layers x 4 heads x (16 + 16) x 4 B
        assert values["mla_kv_expanded_mb.train"]["value"] == pytest.approx(
            2 * 32 * 4 * 4 * 32 * 4 / 1e6)
        assert values["compile_s"]["value"] > 0      # no list: every cell
        for name in ("flash_fwd_ms.train", "grad_sync_mb.train", "mfu.train",
                     "mfu.train_lfm2", "lfm2_flash_roofline.train"):
            assert name not in values, name


def test_a_failed_routing_check_reaches_the_driver_as_nan(monkeypatch):
    """The routing's faults (``test_rehearse_lfm2.py`` holds the judge
    itself to its three) reach the driver through this family too, with the
    module's layer among those judged and its last position filled in."""
    import numpy as np
    from families import glm4_moe_lite as fam
    config = {"check": {"router_differ_share_max": 0.0,
                        "routing_differ_share_max": 0.0}}
    cfg = type("Cfg", (), {"num_layers": 2, "mtp": 1, "experts_total": 4})()
    monkeypatch.setattr(fam, "shapes", lambda config: {
        "experts_first": 0, "top_k": 2})
    monkeypatch.setattr(fam, "program_config", lambda config: cfg)
    monkeypatch.setattr(fam, "system_tree", lambda ref: None)
    monkeypatch.setattr(fam, "routers_of", lambda ref, cfg: (None, None))
    monkeypatch.setattr(fam.glm4_moe_lite_ref, "loss_and_grad_norm",
                        lambda *a, **k: (1.5, 2.5))
    # a trunk layer and the module's, one row of two positions
    mine = np.array([[[[0, 1], [0, 1]]], [[[0, 1], [2, 3]]]])
    sizes = np.array([[2, 2, 0, 0], [1, 1, 1, 1]])
    monkeypatch.setattr(fam, "routing_of", lambda *a: (sizes, mine, None))
    monkeypatch.setattr(fam.glm4_moe_lite_ref, "router_choices",
                        lambda *a, **k: mine)
    # the reference: the trunk's second position differs; its module has
    # one position fewer
    monkeypatch.setattr(
        fam.glm4_moe_lite_ref, "choices",
        lambda *a, **k: (np.array([[[[0, 1], [0, 2]]]]),
                         np.array([[[0, 1]]])))
    tokens = np.zeros((1, 2), np.int32)
    got = fam._checked({}, tokens, micro=1, config=config)
    assert np.isnan(got[0]) and np.isnan(got[1])
    config["check"]["routing_differ_share_max"] = 0.5
    assert fam._checked({}, tokens, micro=1, config=config) == (1.5, 2.5)


def test_the_five_controls_run_through_the_same_comparison():
    """``controls_glm4.py`` at its tiny size: the system passes; a dropped
    row, a router without its bias, a layer without its shared expert and a
    loss without the module's term each fail by the limit aimed at it.
    (Whether bfloat16 logits flip a choice among 256 is the seed's luck; at
    the configuration's size they flip hundreds, PERF.md.)"""
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "controls_glm4.py"), "5"],
        env=dict(os.environ, **CPU), capture_output=True, text=True,
        cwd=ROOT, timeout=900)
    lines = [l for l in done.stdout.splitlines() if l.startswith("5 ")]
    assert len(lines) == 6, done.stdout[-2000:] + done.stderr[-2000:]
    assert lines[0].startswith("5 system:") and lines[0].endswith("passes")
    assert "one_assignment_dropped" in lines[1] and "FAILS" in lines[1]
    assert "without a row" in lines[1]
    assert "router_logits_bf16" in lines[2]
    assert "without_the_bias" in lines[3] and "FAILS" in lines[3]
    assert "float32 router does not make" in lines[3]
    assert "without_the_shared_expert" in lines[4]
    assert "FAILS" in lines[4] and "from the reference's" in lines[4]
    assert "without_the_mtp_term" in lines[5] and "FAILS loss" in lines[5]


def test_the_real_cell_is_entered_as_the_issue_names_it():
    from families import glm4_moe_lite as fam
    bench = _load(ROOT, "BENCHMARK.json")
    cell = [c for c in bench["workloads"] if c["name"] == REAL][0]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "train-fixed-2x8192", 1)
    assert bench["workloads"][-1] is cell and len(bench["workloads"]) == 5
    config = _load(BENCH, "configs", CONFIG + ".json")
    entry = [c for c in bench["configs"] if c["name"] == CONFIG][0]
    assert sorted(entry["reduced"]) == sorted(config["reduced"]) == [
        "n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert entry["source"] == config["source"]
    published = dict(config, **{k: config["published"][k]
                                for k in config["reduced"]})
    # every published number stands but the three that are cut: against the
    # catalog's row, on a machine that has the catalog
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = [r for r in map(json.loads, f)
                   if r["name"] == "GLM-4.7-Flash"][0]
        assert config["source"] == row["source_url"]
        for k, v in row["config"].items():
            assert published[k] == v, k
            assert (config[k] == v) == (k not in config["reduced"]), k
    # the floors of a cut
    assert config["num_hidden_layers"] - config["first_k_dense_replace"] >= 4
    assert config["n_routed_experts"] >= 8
    assert config["vocab_size"] * 8 >= published["vocab_size"]
    assert config["deployment"]["router_width"] == 64
    assert config["assumed"]["expert_bias_std"] == fam.BIAS_STD
    cfg = fam.program_config(config)
    assert (cfg.num_layers, cfg.mtp, cfg.experts_held, cfg.vocab_size,
            cfg.mtp_weight, cfg.routed_scale) == (5, 1, (0, 8), 19360, 0.1,
                                                  1.8)
    assert fam.expert_bias(cfg).shape == (6, 64)
    traffic = _load(BENCH, "traffic", cell["traffic"] + ".json")
    assert traffic["driver"] == "train_steps"
    assert (traffic["sequences_per_chip"], traffic["seq_len"]) == (2, 8192)
    # the cell's metrics have their files, and the files say what the
    # entries say
    own = [m for m in bench["per_layer"] if m.get("workloads") == [REAL]]
    assert [m["name"] for m in own] == [
        "mfu.train_glm4", "mla_flash_time_share.train",
        "mla_flash_roofline.train", "mla_kv_expanded_mb.train"]
    assert bench["per_layer"][-4:] == own
    for metric in own:
        spec = _load(BENCH, "layer_metrics", metric["name"] + ".json")
        for k, v in metric.items():
            assert spec[k] == v, (metric["name"], k)


def test_a_configuration_the_program_does_not_build_is_refused():
    from families import glm4_moe_lite as fam
    config = _load(BENCH, "configs", CONFIG + ".json")
    with pytest.raises(ValueError, match="asks for something else"):
        fam.program_config(dict(config, num_key_value_heads=4))
    with pytest.raises(ValueError, match="come apart"):
        fam.program_config(dict(config, assumed=dict(
            config["assumed"], expert_bias_std=0.05)))


def test_operations_a_token_requires():
    """``flops_glm4.py`` against ISSUE 33's arithmetic at the published
    widths."""
    import flops_glm4
    config = _load(BENCH, "configs", CONFIG + ".json")
    attention = (2048 * 768 + 768 * 5120 + 2048 * 576 + 512 * 8960
                 + 5120 * 2048)
    assert flops_glm4.attention_params(config) == attention
    assert attention == pytest.approx(21.76e6, rel=1e-3)
    dense = 3 * 2048 * 10240
    expert = 3 * 2048 * 1536
    routed = 2048 * 64 + 4 * (8 / 64) * expert + expert
    met = (6 * attention + dense + 5 * routed + 4096 * 2048
           + 2 * 19360 * 2048)
    assert flops_glm4.matmul_params(config) == pytest.approx(met)
    assert met == pytest.approx(352.6e6, rel=1e-3)
    # six layers at half the square: 83.9 MFLOP a token and layer
    forward = 6 * 8192 * (5120 + 5120)
    assert forward / 6 == pytest.approx(83.9e6, rel=1e-3)
    assert flops_glm4.attention_fwd_flops_per_token(config, 8192) == forward
    per_token = flops_glm4.train_flops_per_token(config, 8192)
    assert per_token == pytest.approx(6 * met + 3 * forward)
    assert per_token == pytest.approx(3.63e9, rel=2e-3)
    assert per_token * 16384 == pytest.approx(59.4e12, rel=2e-3)
    # latent attention (projections and kernels) is 63 % of it
    mla = 6 * 6 * attention + 3 * forward
    assert mla / per_token == pytest.approx(0.63, abs=0.005)
    assert flops_glm4.flash_train_flops_per_token(config, 8192) == \
        pytest.approx(3.5 * forward)
    # every head its own key and value: 12 operands of 5120 a layer
    assert flops_glm4.flash_train_bytes_per_token(config, 8192) == \
        6 * 12 * 5120 * 2
