"""``run.py`` end to end on the CPU for the second family, ``sdar_moe``, at a
tiny size: a throw-away cell whose configuration keeps the published ratios
(query heads over fewer key/value heads, a head size apart from the width, 8
experts of which 2 are held, top-4, block length 4, a vocabulary slice) and
whose traffic is ``train-bd-2x4096`` cut to 2 rows of 32 tokens. New files
and entries only, as ``test_rehearse.py`` does it. Not part of tier-1.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_rehearse_sdar.py -q
"""

import json
import os
import shutil

import pytest

from test_rehearse import BENCH, CPU, ROOT, _dump, _load, _run

NAME = "tiny-sdar-bd"


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    tmp = str(tmp_path_factory.mktemp("bench_sdar"))
    shutil.copytree(BENCH, os.path.join(tmp, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = _load(ROOT, "BENCHMARK.json")
    real = "sdar30b-bd-train-dp1"
    config = _load(BENCH, "configs", "sdar-30b-a3b-train.json")
    config.update(hidden_size=32, head_dim=16, num_attention_heads=4,
                  num_key_value_heads=2, moe_intermediate_size=16,
                  num_experts=2, num_experts_per_tok=4, num_hidden_layers=2,
                  vocab_size=256)
    config["deployment"].update(router_width=8, experts_first=2)
    config["assumed"]["mask_id"] = 255
    traffic = _load(BENCH, "traffic", "train-bd-2x4096.json")
    traffic.update(seq_len=32, loss_rel_tol=1e-4, grad_norm_rel_tol=1e-3)
    config["run"].update(compute_dtype="float32")
    bench["configs"].append({
        "name": NAME, "source": "test", "reduced": [], "why": "test",
        "file": f"benchmark/configs/{NAME}.json"})
    _dump(config, tmp, "benchmark", "configs", NAME + ".json")
    _dump(traffic, tmp, "benchmark", "traffic", NAME + ".json")
    bench["workloads"].append({"name": NAME, "config": NAME, "traffic": NAME,
                               "chips": 1, "why": "test"})
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if real in metric.get("workloads", ()):
            metric["workloads"].append(NAME)
    _dump(bench, tmp, "BENCHMARK.json")
    return tmp


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_block_diffusion_cell(checkout, trace):
    done = _run(checkout, NAME, trace, CPU)
    assert done.returncode == 0, done.stdout[-3000:] + done.stderr[-3000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0 and last["metrics"] == {}
    assert "programs compiled in the window: 0" in done.stdout
    assert "[sdar_moe] routing of the check batch" in done.stdout
    if trace:
        # the readers over the program's own gauges find them; the device
        # readers find no device on a CPU and leave their metrics out
        values = json.loads(done.stdout.split(
            "rehearsal values (CPU, not metrics): ")[1].splitlines()[0])
        assert values["bd_tiles_visited_share.train"]["value"] > 0
        assert values["moe_local_assignments.train"]["value"] > 0
        assert values["compile_s"]["value"] > 0      # no list: every cell
        assert values["program_import_init_s"]["value"] > 0
        # a metric whose own file lists the GPT-2 cells, and is held to its
        # entry by test_named_readers, is not this cell's: no twin either
        assert "flash_fwd_ms.train" not in values
        assert not [name for name in values if name.endswith(
            ".train_bd") and name != "mfu.train_bd"]


def test_a_routing_outside_its_limits_fails_the_check():
    """What decides ``correct`` beside loss and gradient norm: a held
    expert's rows counted again from the choices, exactly; the share of a
    layer's choices that a float32 router does not make on the same
    inputs; the share that differ from the reference's."""
    import sys
    import numpy as np
    sys.path.insert(0, BENCH)
    from families import sdar_moe
    rng = np.random.default_rng(0)
    theirs = np.stack([np.stack([rng.permutation(8)[:4] for _ in range(64)])
                       for _ in range(2)]).reshape(2, 1, 64, 4)
    first, held = 2, 2
    rows = lambda c: np.stack([np.bincount(
        l[(l >= first) & (l < first + held)] - first, minlength=held)
        for l in c])
    limits = lambda router, routing: {"router_differ_share_max": router,
                                      "routing_differ_share_max": routing}
    judge = lambda mine, again, sizes, lim: sdar_moe.routing_faults(
        mine, theirs, again, sizes, first, lim)
    faults, router, differ = judge(theirs, theirs, rows(theirs),
                                   limits(0.0, 0.0))
    assert faults == [] and router.tolist() == differ.tolist() == [0, 0]
    # one assignment of the last layer without its row
    short = rows(theirs)
    short[1, 0] -= 1
    faults, _, _ = judge(theirs, theirs, short, limits(0.0, 0.0))
    assert len(faults) == 1 and "without a row" in faults[0]
    assert "[0, 1]" in faults[0]
    # three choices of 256 moved to an expert the reference did not choose
    mine = theirs.copy()
    for pos in (3, 17, 40):
        absent = sorted(set(range(8)) - set(theirs[0, 0, pos]))[0]
        mine[0, 0, pos, 0] = absent
    # ... by what came before the router: inside 2 %, outside 1 %
    faults, router, differ = judge(mine, mine, rows(mine), limits(0.0, 0.02))
    assert faults == [] and (router.tolist(), differ.tolist()) == (
        [0, 0], [3, 0])
    faults, _, _ = judge(mine, mine, rows(mine), limits(0.0, 0.01))
    assert len(faults) == 1 and "differ from the reference" in faults[0]
    # ... by the router itself: the same inputs routed again say otherwise
    faults, router, _ = judge(mine, theirs, rows(mine), limits(0.001, 0.02))
    assert router.tolist() == [3, 0]
    assert len(faults) == 1 and "float32 router" in faults[0]


def test_a_failed_routing_check_reaches_the_driver_as_nan(monkeypatch):
    import sys
    import numpy as np
    sys.path.insert(0, BENCH)
    from families import sdar_moe
    config = {"check": {"router_differ_share_max": 0.0,
                        "routing_differ_share_max": 0.0}}
    monkeypatch.setattr(sdar_moe, "shapes", lambda config: {
        "experts_first": 0, "top_k": 2, "norm_topk": True})
    monkeypatch.setattr(sdar_moe, "program_config", lambda config: None)
    monkeypatch.setattr(sdar_moe, "system_tree", lambda ref: None)
    monkeypatch.setattr(sdar_moe.sdar_moe_ref, "loss_and_grad_norm",
                        lambda *a, **k: (1.5, 2.5))
    mine = np.array([[[[0, 1]]]])
    monkeypatch.setattr(sdar_moe, "routing_of",
                        lambda *a: (np.array([[1, 1]]), mine, None))
    monkeypatch.setattr(sdar_moe.sdar_moe_ref, "router_choices",
                        lambda *a, **k: mine)
    monkeypatch.setattr(sdar_moe.sdar_moe_ref, "choices",
                        lambda *a, **k: np.array([[[[0, 2]]]]))
    ref = {"h": {"moe": {"router": None}}}
    tokens = np.zeros((1, 4), np.int32)
    got = sdar_moe._checked(ref, tokens, micro=1, config=config)
    assert np.isnan(got[0]) and np.isnan(got[1])
    config["check"]["routing_differ_share_max"] = 0.5
    assert sdar_moe._checked(ref, tokens, micro=1, config=config) == \
        (1.5, 2.5)


def test_the_controls_run_through_the_same_comparison():
    """``controls_sdar.py`` at its tiny size: the system
    passes, and its own rows with one assignment taken away fail. (Whether
    bfloat16 logits flip a choice among 512 is the seed's luck; at the
    configuration's size they flip hundreds, PERF.md.)"""
    import subprocess
    import sys
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "controls_sdar.py"), "5"],
        env=dict(os.environ, **CPU), capture_output=True, text=True,
        cwd=ROOT, timeout=600)
    lines = [l for l in done.stdout.splitlines() if l.startswith("5 ")]
    assert len(lines) == 5, done.stdout[-2000:] + done.stderr[-2000:]
    assert lines[0].startswith("5 system:") and lines[0].endswith("passes")
    assert "one_assignment_dropped" in lines[1] and "FAILS" in lines[1]
    assert "without a row" in lines[1]


def test_the_real_cell_is_entered_as_the_issue_names_it():
    bench = _load(ROOT, "BENCHMARK.json")
    cell = [c for c in bench["workloads"]
            if c["name"] == "sdar30b-bd-train-dp1"][0]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "sdar-30b-a3b-train", "train-bd-2x4096", 1)
    config = _load(BENCH, "configs", "sdar-30b-a3b-train.json")
    entry = [c for c in bench["configs"] if c["name"] == cell["config"]][0]
    assert sorted(entry["reduced"]) == sorted(config["reduced"]) == [
        "num_experts", "num_hidden_layers", "vocab_size"]
    # every published width stands; the floors of a cut hold
    assert (config["hidden_size"], config["num_attention_heads"],
            config["num_key_value_heads"], config["head_dim"],
            config["moe_intermediate_size"],
            config["deployment"]["router_width"],
            config["num_experts_per_tok"]) == (2048, 32, 4, 128, 768, 128, 8)
    assert config["num_hidden_layers"] >= 4 and config["num_experts"] >= 16
    assert config["vocab_size"] * 8 >= config["published"]["vocab_size"]
    traffic = _load(BENCH, "traffic", cell["traffic"] + ".json")
    assert traffic["driver"] == "train_steps"
    assert traffic["sequences_per_chip"] * traffic["seq_len"] == 8192


def test_operations_a_clean_token_requires():
    import sys
    sys.path.insert(0, BENCH)
    import flops_sdar
    config = _load(BENCH, "configs", "sdar-30b-a3b-train.json")
    layers = config["num_hidden_layers"]
    # 18.87 M attention + 0.26 M router + 8 x 16/128 x 4.72 M experts
    assert flops_sdar.layer_matmul_params(config) == pytest.approx(
        18_874_368 + 262_144 + 4_718_592)
    per_token = flops_sdar.train_flops_per_token(config, 4096)
    dense = 6 * (2 * layers * 23_855_104 + 18_992 * 2048)
    attention = 3 * layers * 4 * 4096 * (4096 + 4)
    assert per_token == pytest.approx(dense + attention)
    assert flops_sdar.flash_train_flops_per_token(config, 4096) == \
        pytest.approx(3.5 * attention / 3)
