"""The trace reducer on synthetic event lists and on real operation names.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_reduce.py -q

Not part of the repo's tier-1 tests: the benchmark checks itself.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402

from reduce import xplane  # noqa: E402

# operation names as the v5e's trace gives them (PERF.md section 3)
FUSION = ("%fusion.10 = f32[50304,1024]{1,0:T(8,128)} fusion(bf16[8,1023,"
          "50304]{2,1,0:T(8,128)(2,1)} %select_add_fusion, bf16[8,1024,1024]"
          "{1,2,0:T(8,128)(2,1)S(1)} %copy-done.3), kind=kOutput")
FLASH = ("%attn.189 = (bf16[128,1024,64]{2,1,0:T(8,128)(2,1)S(1)}, f32[128,"
         "1024,1]{2,1,0:T(8,128)}) custom-call(bf16[128,1024,64]{2,1,0:T(8,"
         "128)(2,1)} %bitcast.3271), custom_call_target=\"tpu_custom_call\"")
CONCAT = ("%custom-call.429 = f32[1024,3072]{1,0:T(8,128)S(1)} custom-call("
          "f32[256,3072]{1,0:T(8,128)S(1)} %slice-done.1484), "
          "custom_call_target=\"ConcatBitcast\"")
COPY_START = ("%copy-start.416 = (f32[8,1024]{1,0:T(8,128)}, f32[8,1024]"
              "{1,0:T(8,128)S(1)}, u32[]{:S(2)}) copy-start(f32[8,1024]{1,0:"
              "T(8,128)S(1)} %get-tuple-element)")
AR_START = ("%all-reduce-start.3 = f32[1024,3072]{1,0:T(8,128)} "
            "all-reduce-start(f32[1024,3072]{1,0:T(8,128)} %fusion.7), "
            "channel_id=4, replica_groups={{0,1,2,3}}, to_apply=%add")
AR_DONE = ("%all-reduce-done.3 = f32[1024,3072]{1,0:T(8,128)} "
           "all-reduce-done(f32[1024,3072]{1,0:T(8,128)} "
           "%all-reduce-start.3)")
AR_FUSED = ("%all-reduce-scatter.2 = f32[256,3072]{1,0:T(8,128)} fusion("
            "f32[1024,3072]{1,0:T(8,128)} %fusion.9), kind=kCustom")


@pytest.mark.parametrize("name,opcode", [
    (FUSION, "fusion"), (FLASH, "custom-call"), (CONCAT, "custom-call"),
    (COPY_START, "copy-start"), (AR_START, "all-reduce-start"),
    (AR_DONE, "all-reduce-done"), (AR_FUSED, "fusion"),
    ("fusion.10", "fusion"), ("%all-reduce.1", "all-reduce"),
    ("dot", "dot"),
])
def test_opcode_of_real_names(name, opcode):
    assert xplane.hlo_opcode(name) == opcode


@pytest.mark.parametrize("name,collective", [
    (FUSION, False), (FLASH, False), (COPY_START, False),
    (AR_START, True), (AR_DONE, True), (AR_FUSED, True),
    ("%all-gather.5 = bf16[8]{0} all-gather(bf16[2]{0} %x)", True),
    ("%collective-permute-done.1 = f32[4]{0} collective-permute-done("
     "f32[4]{0} %s)", True),
    ("%reduce.4 = f32[] reduce(f32[8]{0} %x, f32[] %c), to_apply=%add",
     False),
])
def test_collectives_are_recognised(name, collective):
    assert xplane.is_collective(name) is collective


def test_busy_union_counts_overlaps_once():
    ops = [("a", 0, 10), ("b", 5, 10), ("c", 30, 5), ("d", 32, 1)]
    assert xplane.union(ops) == [(0, 15), (30, 35)]
    assert xplane.busy_ns(ops) == 20
    assert xplane.idle_share(ops, 40) == pytest.approx(0.5)


def test_clip_cuts_events_to_the_window():
    ops = [("a", 0, 10), ("b", 20, 10), ("c", 50, 10)]
    assert xplane.clip(ops, 5, 25) == [("a", 5, 5), ("b", 20, 5)]


def test_subtract():
    assert xplane.subtract([(0, 10), (20, 30)], [(5, 22), (25, 26)]) == \
        [(0, 5), (22, 25), (26, 30)]
    assert xplane.subtract([(0, 10)], []) == [(0, 10)]


def test_exposed_collective_with_compute_over_half_of_it():
    # an asynchronous all-reduce of 100 ns; a fusion overlaps its first
    # half; the done instruction waits through the second half
    ops = [(FUSION, 0, 50), (AR_START, 0, 1), (AR_DONE, 50, 50)]
    asyncs = [(AR_START, 0, 100)]
    every, exposed = xplane.collective_ns(ops, asyncs)
    assert (every, exposed) == (100, 50)


def test_no_collective_reads_zero():
    assert xplane.collective_ns([(FUSION, 0, 50), (FLASH, 50, 10)],
                                [(COPY_START, 0, 40)]) == (0, 0)


def test_per_module_means_and_whole_runs():
    modules = [("jit__decode_raw(123)", 0, 10), ("jit__decode_raw(123)", 20,
                                                 30),
               ("jit__prefill_raw(9)", 60, 100)]
    assert xplane.module_stats(modules, "_decode_raw") == {
        "count": 2, "total_ns": 40, "mean_ns": 20}
    assert xplane.module_stats(modules, "_prefill_raw")["mean_ns"] == 100
    assert xplane.module_stats(modules, "train_step") is None
    win = {"modules": modules, "ops": [("x", 0, 200)], "asyncs": []}
    runs, ops, _ = xplane.whole_runs(win, "_decode_raw")
    assert len(runs) == 2 and ops == [("x", 0, 50)]
    assert xplane.whole_runs(win, "train_step") is None


def test_custom_call_time_by_target():
    ops = [(FLASH, 0, 10), (CONCAT, 10, 1), (FUSION, 11, 20), (FLASH, 31, 10)]
    assert xplane.kernel_ns(ops, "custom-call") == 21
    assert xplane.kernel_ns(ops, "custom-call", "tpu_custom_call") == 20
    assert xplane.top_ops(ops, 2) == [
        ("custom-call tpu_custom_call (%attn.*, all instances)", 20),
        (FUSION, 20)]


def test_gaps_are_named_by_the_host_span_over_them():
    ops = [("a", 0, 10), ("b", 40, 10), ("c", 55, 45), ("d", 130, 10)]
    host = [("bench:put_batch", 8, 30), ("bench:wait", 38, 4)]
    modules = [("jit_train_step(1)", 38, 70)]
    gaps = xplane.idle_gaps(ops, 0, 140, host, modules, n=10)
    assert gaps == [("bench:put_batch", 30), ("no bench span", 30),
                    ("inside the program", 5)]


def test_window_and_summary_from_a_loaded_trace():
    trace = {
        "devices": {
            "/device:TPU:0": {
                "XLA Modules": [("jit_train_step(7)", 99, 1),   # a stub
                                ("jit_train_step(7)", 100, 100),
                                ("jit_train_step(7)", 200, 100),
                                ("jit_train_step(7)", 290, 100),
                                ("jit_train_step(7)", 390, 5)],
                "XLA Ops": [(FUSION, 100, 60), (FLASH, 160, 30),
                            (FUSION, 200, 90), (FUSION, 290, 100)],
                "Async XLA Ops": [(COPY_START, 100, 20)]},
            "/device:TPU:1": {"XLA Ops": [(FUSION, 100, 50)]}},
        "host": [("bench:traced", 100, 200), ("bench:wait", 190, 10)]}
    win = xplane.window(trace)
    assert (win["lo"], win["hi"]) == (100, 300)
    # the trace's first and last runs and the one cut by the window go
    assert len(win["modules"]) == 2
    s = xplane.summarize(win)
    assert s["busy_s"] == pytest.approx(190e-9)
    assert s["busy_s_mean"] == pytest.approx((190e-9 + 50e-9) / 2)
    assert s["modules"]["jit_train_step"]["count"] == 2
    assert s["idle_gaps"][0] == ["bench:wait", pytest.approx(10e-9)]
    assert xplane.window({"devices": {}, "host": []}) is None
