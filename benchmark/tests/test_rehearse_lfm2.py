"""``run.py`` end to end on the CPU for the third family, ``lfm2_moe``, at a
tiny size: a throw-away cell whose configuration keeps the published kinds
of layer (a conv operator with a dense SwiGLU, an attention layer and a conv
layer with routed experts, 8 experts of which 2 are held, top-4 by score +
bias, a vocabulary slice) and whose traffic is ``train-fixed-4x8192`` cut to
2 rows of 32 tokens. New files and entries only, as ``test_rehearse.py`` does
it. Not part of tier-1.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_rehearse_lfm2.py -q
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

from test_rehearse import BENCH, CPU, ROOT, _dump, _load, _run

NAME = "tiny-lfm2"
REAL = "lfm2-24b-train-dp1"
sys.path.insert(0, BENCH)


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    import controls_lfm2
    tmp = str(tmp_path_factory.mktemp("bench_lfm2"))
    shutil.copytree(BENCH, os.path.join(tmp, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = _load(ROOT, "BENCHMARK.json")
    config = _load(BENCH, "configs", "lfm2-24b-a2b-train.json")
    config.update(controls_lfm2.TINY)
    config["deployment"].update(router_width=8, experts_first=2)
    config["run"].update(compute_dtype="float32")
    traffic = _load(BENCH, "traffic", "train-fixed-4x8192.json")
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
def test_rehearsal_of_the_hybrid_cell(checkout, trace):
    done = _run(checkout, NAME, trace, CPU)
    assert done.returncode == 0, done.stdout[-3000:] + done.stderr[-3000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0 and last["metrics"] == {}
    assert "programs compiled in the window: 0" in done.stdout
    assert "[lfm2_moe] routing of the check batch" in done.stdout
    assert "weights and selection bias from the fixed seed" in done.stdout
    if trace:
        # the readers over the program's own gauges find them; the device
        # readers find no device on a CPU and leave their metrics out
        values = json.loads(done.stdout.split(
            "rehearsal values (CPU, not metrics): ")[1].splitlines()[0])
        assert 0 < values["moe_bias_moved_share.train"]["value"] < 100
        assert values["moe_local_assignments.train"]["value"] > 0
        assert 0 < values["causal_tiles_visited_share.train"]["value"] <= 100
        assert values["compile_s"]["value"] > 0      # no list: every cell
        assert values["program_import_init_s"]["value"] > 0
        # the five that test_named_readers holds to their files are not
        # this cell's, and the other families' own metrics neither
        for name in ("flash_fwd_ms.train", "grad_sync_mb.train",
                     "mfu.train", "mfu.train_bd",
                     "bd_tiles_visited_share.train"):
            assert name not in values, name


def test_the_three_faults_of_a_routing_fail_the_check():
    """What decides ``correct`` beside loss and gradient norm, on this
    family's rule: a dropped row; choices a float32 router with the same
    bias does not make on the same inputs (bfloat16 logits at the cell's
    size; here a router that forgot the bias, which is the same fault
    writ large); choices the reference did not make."""
    import numpy as np
    from families import lfm2_moe
    rng = np.random.default_rng(0)
    scores = rng.random((2, 1, 64, 8))
    bias = 0.3 * rng.standard_normal((2, 1, 1, 8))
    top = lambda s: np.argsort(-s, axis=-1)[..., :4]
    theirs, plain = top(scores + bias), top(scores)
    first, held = 2, 2
    rows = lambda c: np.stack([np.bincount(
        l[(l >= first) & (l < first + held)] - first, minlength=held)
        for l in c])
    limits = {"router_differ_share_max": 0.01,
              "routing_differ_share_max": 0.02}
    judge = lambda mine, again, sizes: lfm2_moe.routing_faults(
        mine, theirs, again, sizes, first, limits)
    assert judge(theirs, theirs, rows(theirs))[0] == []
    short = rows(theirs)
    short[1, 1] -= 1
    faults, _, _ = judge(theirs, theirs, short)
    assert len(faults) == 1 and "without a row" in faults[0]
    # the router that selects without the bias: against the same inputs
    # routed with it, and against the reference
    faults, router, differ = judge(plain, theirs, rows(plain))
    assert len(faults) == 2 and (router > 2).all() and (differ > 5).all()
    assert "float32 router" in faults[0] and "reference" in faults[1]
    # a few choices moved by what came before the router: inside 2 %
    mine = theirs.copy()
    for pos in (3, 17, 40):
        mine[0, 0, pos, 0] = sorted(set(range(8)) - set(theirs[0, 0, pos]))[0]
    faults, router, differ = judge(mine, mine, rows(mine))
    assert faults == [] and differ.tolist() == [3, 0]


def test_a_failed_routing_check_reaches_the_driver_as_nan(monkeypatch):
    import numpy as np
    from families import lfm2_moe
    config = {"check": {"router_differ_share_max": 0.0,
                        "routing_differ_share_max": 0.0}}
    cfg = type("Cfg", (), {"num_layers": 2, "experts_total": 4})()
    monkeypatch.setattr(lfm2_moe, "shapes", lambda config: {
        "experts_first": 0, "top_k": 2})
    monkeypatch.setattr(lfm2_moe, "program_config", lambda config: cfg)
    monkeypatch.setattr(lfm2_moe, "system_tree", lambda ref: None)
    monkeypatch.setattr(lfm2_moe, "routers_of", lambda ref, cfg: (None, None))
    monkeypatch.setattr(lfm2_moe.lfm2_moe_ref, "loss_and_grad_norm",
                        lambda *a, **k: (1.5, 2.5))
    mine = np.array([[[[0, 1]]]])
    monkeypatch.setattr(lfm2_moe, "routing_of",
                        lambda *a: (np.array([[1, 1]]), mine, None))
    monkeypatch.setattr(lfm2_moe.lfm2_moe_ref, "router_choices",
                        lambda *a, **k: mine)
    monkeypatch.setattr(lfm2_moe.lfm2_moe_ref, "choices",
                        lambda *a, **k: np.array([[[[0, 2]]]]))
    tokens = np.zeros((1, 4), np.int32)
    got = lfm2_moe._checked({}, tokens, micro=1, config=config)
    assert np.isnan(got[0]) and np.isnan(got[1])
    config["check"]["routing_differ_share_max"] = 0.5
    assert lfm2_moe._checked({}, tokens, micro=1, config=config) == \
        (1.5, 2.5)


def test_the_controls_run_through_the_same_comparison():
    """``controls_lfm2.py`` at its tiny size: the system passes; its own rows
    with one assignment taken away fail, and so does the router that selects
    without the bias, by the limit that is aimed at it. (Whether bfloat16
    logits flip a choice among 256 is the seed's luck; at the
    configuration's size they flip hundreds, PERF.md.)"""
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "controls_lfm2.py"), "5"],
        env=dict(os.environ, **CPU), capture_output=True, text=True,
        cwd=ROOT, timeout=600)
    lines = [l for l in done.stdout.splitlines() if l.startswith("5 ")]
    assert len(lines) == 6, done.stdout[-2000:] + done.stderr[-2000:]
    assert lines[0].startswith("5 system:") and lines[0].endswith("passes")
    assert "one_assignment_dropped" in lines[1] and "FAILS" in lines[1]
    assert "without a row" in lines[1]
    assert "without_the_bias" in lines[3] and "FAILS" in lines[3]
    assert "float32 router does not make" in lines[3]


def test_the_real_cell_is_entered_as_the_issue_names_it():
    from families import lfm2_moe
    bench = _load(ROOT, "BENCHMARK.json")
    cell = [c for c in bench["workloads"] if c["name"] == REAL][0]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "lfm2-24b-a2b-train", "train-fixed-4x8192", 1)
    config = _load(BENCH, "configs", "lfm2-24b-a2b-train.json")
    entry = [c for c in bench["configs"] if c["name"] == cell["config"]][0]
    assert sorted(entry["reduced"]) == sorted(config["reduced"]) == [
        "layer_types", "num_dense_layers", "num_experts",
        "num_hidden_layers", "vocab_size"]
    # every published width stands; the floors of a cut hold
    assert (config["hidden_size"], config["num_attention_heads"],
            config["num_key_value_heads"], config["conv_L_cache"],
            config["intermediate_size"], config["moe_intermediate_size"],
            config["deployment"]["router_width"],
            config["num_experts_per_tok"], config["use_expert_bias"]) == (
                2048, 32, 8, 3, 11776, 1536, 64, 4, True)
    kinds = config["layer_types"]
    assert len(kinds) == config["num_hidden_layers"] == 5
    assert config["num_dense_layers"] == 1
    routed = kinds[1:]          # one whole period after the dense layer
    assert sorted(routed) == ["conv", "conv", "conv", "full_attention"]
    assert config["num_experts"] >= 8
    assert config["vocab_size"] * 8 >= config["published"]["vocab_size"]
    assert config["assumed"]["expert_bias_std"] == lfm2_moe.BIAS_STD
    traffic = _load(BENCH, "traffic", cell["traffic"] + ".json")
    assert traffic["driver"] == "train_steps"
    assert (traffic["sequences_per_chip"], traffic["seq_len"]) == (4, 8192)
    # the cell's metrics have their files, and the files say what the
    # entries say
    for metric in bench["per_layer"]:
        if metric.get("workloads") == [REAL]:
            spec = _load(BENCH, "layer_metrics", metric["name"] + ".json")
            for k, v in metric.items():
                assert spec[k] == v, (metric["name"], k)


def test_operations_a_token_requires():
    import flops_lfm2
    config = _load(BENCH, "configs", "lfm2-24b-a2b-train.json")
    conv = 2048 * 6144 + 2048 * 2048
    attention = 2 * 2048 * 2048 + 2 * 2048 * 512
    dense = 3 * 2048 * 11776
    routed = 2048 * 64 + 4 * (8 / 64) * 3 * 2048 * 1536
    head = 8192 * 2048
    met = 4 * conv + attention + dense + 4 * routed + head
    assert flops_lfm2.matmul_params(config) == pytest.approx(met)
    assert met == pytest.approx(186.1e6, rel=1e-3)
    forward = 2 * 8192 * 2048          # one attention layer, half the square
    assert flops_lfm2.attention_fwd_flops_per_token(config, 8192) == forward
    assert flops_lfm2.train_flops_per_token(config, 8192) == pytest.approx(
        6 * met + 3 * forward)
    assert flops_lfm2.flash_train_flops_per_token(config, 8192) == \
        pytest.approx(3.5 * forward)
    # 8 key/value heads serve 32: q, o, do, dq at 2048, k, v, dk, dv at 512
    assert flops_lfm2.flash_train_bytes_per_token(config, 8192) == \
        2 * (6 * 2048 + 6 * 512)
    assert flops_lfm2.short_conv_bytes_per_token(config) == 4 * 11 * 2048 * 2
