"""The program's own spans, scopes, sync manifest and set-up ledger
(``horovod_tpu/tracing.py``), on the CPU: every name emitted is in the
table, the lowered README step carries the trainer's scopes and the three
kernel names, the manifest counts what the step hands to all-reduce, the
ledger books jax's trace / lower / backend seconds under the function's
name, and one ``step_once`` leaves its phase spans on the profiler's host
plane in the table's order."""

import ast
import dataclasses
import glob
import importlib
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu import tracing
from horovod_tpu.models.gpt2 import GPT2, GPT2Config, loss_fn

PKG = os.path.dirname(os.path.abspath(hvd.__file__))
TRAINER_SCOPES = ["hvd/value_and_grad/sync", "hvd/optimizer/sync",
                  "hvd/optimizer/update", "hvd/fusion/pack",
                  "hvd/fusion/unpack", "gpt2/loss_head", "gpt2/attn",
                  "gpt2/mlp", "gpt2/lm_head", "flash/layout"]
KERNELS = ["flash_fwd", "flash_dq", "flash_dkv"]
XLAS_OWN = {"ragged-dot"}   # a kernel XLA names: no call here emits it
SDAR_SCOPES = ["sdar/attn", "sdar/block", "moe/route", "moe/experts",
               "sdar/loss_head"]
LFM2_SCOPES = ["lfm2/block", "lfm2/shortconv", "lfm2/attn", "lfm2/dense_mlp",
               "moe/route", "moe/experts", "lfm2/loss_head"]
GLM4_SCOPES = ["glm4/block", "glm4/mla_down", "glm4/mla_up", "glm4/attn",
               "glm4/dense_mlp", "glm4/shared_expert", "glm4/mtp",
               "glm4/loss_head", "moe/route", "moe/experts"]
SMALLTHINKER_SCOPES = ["smallthinker/block", "smallthinker/attn_global",
                       "smallthinker/attn_window", "smallthinker/loss_head",
                       "moe/route", "moe/experts"]
ROUTING = ["moe_rows_bound", "moe_rows_tight", "moe_rows_overflow_layers",
           "window_tiles_visited", "window_tiles_total",
           "bd_tiles_visited", "bd_tiles_total",
           "causal_tiles_visited", "causal_tiles_total",
           "mla_kv_expanded_bytes", "mla_latent_bytes", "mtp_modules",
           "flash_bwd_kernels", "flash_bwd_vmem_bytes",
           "moe_local_assignments", "moe_load_max_over_mean",
           "moe_bias_moved_share"]
ENGINE_PHASES = ["sweep", "admit", "build", "dispatch", "readback", "commit"]


def _gauges(name, **labels):
    """{scope: value} of the manifest gauge ``name`` with these labels."""
    return {s["labels"]["scope"]: s["value"]
            for s in hvd.metrics.snapshot()["gauges"].get(name, ())
            if all(s["labels"].get(k) == v for k, v in labels.items())}


def _program_gauge(name, program):
    """The values of the gauge ``name{program}`` (one, or none yet)."""
    return [s["value"]
            for s in hvd.metrics.snapshot()["gauges"].get(name, ())
            if s["labels"] == {"program": program}]


def _counter(name, **labels):
    return sum(s["value"]
               for s in hvd.metrics.snapshot()["counters"].get(name, ())
               if all(s["labels"].get(k) == v for k, v in labels.items()))


# ---------------------------------------------------------------------------
# the table
# ---------------------------------------------------------------------------

def _calls(tree):
    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            f = node.func
            yield node, (f.attr if isinstance(f, ast.Attribute)
                         else getattr(f, "id", ""))


def _emitted_names():
    """(file, line, name) of every span, scope, kernel name and tracing
    counter in the package's source, and the places that go round the
    facility."""
    used, strays = [], []
    for path in glob.glob(os.path.join(PKG, "**", "*.py"), recursive=True):
        rel = os.path.relpath(path, PKG)
        tree = ast.parse(open(path).read())
        for node, fn in _calls(tree):
            owner = getattr(getattr(node.func, "value", None), "id", "")
            if fn in ("TraceAnnotation", "named_scope") \
                    and rel != "tracing.py":
                strays.append((rel, node.lineno, fn))
            if fn in ("span", "scope", "timed") \
                    and owner in ("tracing", "_tracing"):
                arg = node.args[0]
                if isinstance(arg, ast.BinOp):      # "engine." + phase
                    assert rel == os.path.join("serving", "engine.py")
                    used += [(rel, node.lineno, arg.left.value + p)
                             for p in ENGINE_PHASES]
                else:
                    assert isinstance(arg, ast.Constant), (rel, node.lineno)
                    used.append((rel, node.lineno, arg.value))
                if fn == "timed":
                    family = node.args[1].value
                    used += [(rel, node.lineno, family + "_seconds_total"),
                             (rel, node.lineno, family + "_total")]
            if fn == "pallas_call":
                used += [(rel, node.lineno, kw.value.value)
                         for kw in node.keywords if kw.arg == "name"]
            if rel == "tracing.py" and owner == "_metrics" \
                    and fn in ("counter", "gauge"):
                arg = node.args[0]
                if isinstance(arg, ast.Constant):
                    used.append((rel, node.lineno, arg.value))
                elif isinstance(arg, ast.Name):     # a key of the routing
                    assert arg.id == "key"          # manifest, as noted
                    used += [(rel, node.lineno, k) for k in tracing._ROUTING]
                else:                               # "grad_sync_" + what
                    used += [(rel, node.lineno, arg.left.value + w)
                             for w in tracing._COUNTS] \
                        if isinstance(arg.left, ast.Constant) else []
        if rel == "__init__.py":
            used.append((rel, 0, "import_seconds"))
    return used, strays


def test_every_name_emitted_is_in_the_table():
    used, strays = _emitted_names()
    assert not strays, f"spans or scopes that go round tracing.py: {strays}"
    unlisted = [u for u in used if u[2] not in tracing.NAMES]
    assert not unlisted, f"emitted but not in tracing.NAMES: {unlisted}"
    names = {u[2] for u in used}
    assert set(TRAINER_SCOPES) | set(KERNELS) <= names
    assert set(SDAR_SCOPES) | set(LFM2_SCOPES) | set(GLM4_SCOPES) <= names
    assert set(SMALLTHINKER_SCOPES) <= names
    assert set(ROUTING) <= names
    assert {"engine." + p for p in ENGINE_PHASES} <= names
    # and the table lists nothing that is not emitted
    assert set(tracing.NAMES) - names == XLAS_OWN, set(tracing.NAMES) - names


def test_the_table_names_a_layer_and_a_reader_for_every_row():
    for name, row in tracing.NAMES.items():
        assert row.kind in ("span", "scope", "kernel", "counter", "gauge")
        assert row.layer and row.covers and row.feeds, name
        assert "\n" not in row.covers
    phases = [n for n in tracing.NAMES if n.startswith("engine.")]
    assert phases == ["engine.step"] + ["engine." + p for p in ENGINE_PHASES]
    # a scope's device time is read by a metric or printed by every traced
    # run; nothing is summed by hand any more
    for name, row in tracing.NAMES.items():
        assert "by hand" not in row.feeds, name
        if row.kind == "scope":
            assert "xprof only" not in row.feeds, name


def test_the_profiler_knob_is_gone():
    from horovod_tpu import confbus, config
    assert not hasattr(config.Config(), "trace_jax_profiler")
    assert "HOROVOD_TRACE_JAX_PROFILER" not in confbus._IMMUTABLE_FIELDS


# ---------------------------------------------------------------------------
# the README step, lowered
# ---------------------------------------------------------------------------

def _asked(program):
    """``scope_table(program)`` and the compiled text it read."""
    read, texts = tracing._read_scopes, []
    tracing._read_scopes = lambda text: texts.append(text) or read(text)
    try:
        table = tracing.scope_table(program)
    finally:
        tracing._read_scopes = read
    return table, texts[0]


def _step(devices, readme=True, touch=False, table=False, **changes):
    """The README train step (or the same with plain jax.value_and_grad)
    lowered on ``devices``: its text, its program name, the manifest it
    left, and the bytes of its parameter tree. ``touch`` multiplies the
    gradients by one between the two syncs: new objects, so that both
    passes lower, as they did before a pass could be skipped. ``table``:
    its scope table too, and the compiled text that was read from.
    ``changes`` are to the model's configuration (flash attention under
    ``remat=dots``, two layers)."""
    hvd.init(devices=devices)
    try:
        cfg = dataclasses.replace(
            GPT2Config.tiny(attention="flash", remat=True,
                            remat_policy="dots"), **changes)
        model = GPT2(cfg)
        tokens = jnp.zeros((2 * len(devices), cfg.max_seq_len), jnp.int32)
        params = model.init(jax.random.PRNGKey(0), tokens[:1])
        opt = hvd.DistributedOptimizer(optax.adamw(1e-3))
        vg = hvd.value_and_grad if readme else jax.value_and_grad

        def train_step(params, opt_state, tokens):
            loss, grads = vg(
                lambda p: loss_fn(model.apply(p, tokens), tokens))(params)
            if touch:
                grads = jax.tree_util.tree_map(lambda g: g * 1, grads)
            updates, opt_state = opt.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state, loss

        step = hvd.spmd(train_step, in_specs=(P(), P(), P("hvd")),
                        out_specs=(P(), P(), P()))
        assert type(step) is type(jax.jit(lambda: 0))   # the bare jit
        lowered = step.lower(params, opt.init(params), tokens)
        asked = _asked("train_step") if table else (None, None)
        return {
            "table": asked[0], "compiled": asked[1],
            "text": lowered.as_text(debug_info=True),
            "module": lowered.as_text().split("{", 1)[0],
            "tree_bytes": sum(x.size * x.dtype.itemsize for x in
                              jax.tree_util.tree_leaves(params)),
            "bytes": _gauges("grad_sync_bytes", program="train_step"),
            "buckets": _gauges("grad_sync_buckets", program="train_step"),
            "passes": _gauges("grad_sync_passes", program="train_step"),
            "skipped": _gauges("grad_sync_skipped", program="train_step"),
            "all_reduces": lowered.as_text().count("stablehlo.all_reduce"),
            "saved": _program_gauge("flash_residuals_saved", "train_step"),
            "causal_tiles": [_program_gauge(name, "train_step") for name in
                             ("causal_tiles_visited", "causal_tiles_total")],
            "flash_bwd": [_program_gauge(name, "train_step") for name in
                          ("flash_bwd_kernels", "flash_bwd_vmem_bytes")],
        }
    finally:
        hvd.init()          # back onto the session's 8 CPU devices


@pytest.fixture(scope="module")
def readme_step():
    return _step(jax.devices()[:2], table=True)


@pytest.fixture(scope="module")
def twice_step():
    return _step(jax.devices()[:2], touch=True)


@pytest.mark.parametrize("name", TRAINER_SCOPES + KERNELS)
def test_lowered_readme_step_carries_the_name(readme_step, twice_step, name):
    assert name in twice_step["text"]
    # the optimizer's pass is skipped on the README path: nothing is
    # lowered in its scope, so the text has no operation to carry it
    assert (name in readme_step["text"]) == (name != "hvd/optimizer/sync")


def test_the_program_keeps_its_name(readme_step):
    assert "@jit_train_step" in readme_step["module"]


def test_manifest_of_the_readme_step_is_one_pass(readme_step):
    """hvd.value_and_grad averages; DistributedOptimizer.update is handed
    the very leaves it returned and lowers nothing: both scopes keep their
    entry, the second with zeros and one skipped pass."""
    tree = readme_step["tree_bytes"]
    assert readme_step["passes"] == {"hvd/value_and_grad/sync": 1,
                                     "hvd/optimizer/sync": 0}
    assert readme_step["bytes"] == {"hvd/value_and_grad/sync": tree,
                                    "hvd/optimizer/sync": 0}
    assert readme_step["skipped"] == {"hvd/value_and_grad/sync": 0,
                                      "hvd/optimizer/sync": 1}
    assert readme_step["buckets"]["hvd/value_and_grad/sync"] >= 1
    assert readme_step["buckets"]["hvd/optimizer/sync"] == 0


def test_lowered_readme_step_holds_half_the_all_reduces(readme_step,
                                                        twice_step):
    """Beside the same step with its gradients touched in between (two
    passes, what every README step lowered before): the loss's one
    all-reduce apart, half as many."""
    twice = twice_step
    assert twice["passes"] == {"hvd/value_and_grad/sync": 1,
                               "hvd/optimizer/sync": 1}
    assert not any(twice["skipped"].values())
    assert sum(twice["bytes"].values()) == 2 * twice["tree_bytes"]
    once = readme_step["all_reduces"] - 1
    assert once >= 1 and twice["all_reduces"] - 1 == 2 * once


def test_manifest_is_the_last_lowering_not_a_sum():
    """jax.value_and_grad + DistributedOptimizer syncs once; lowering that
    step after the README one (same program name) replaces its manifest,
    the scope that fell away reading 0."""
    _step(jax.devices()[:2])
    one = _step(jax.devices()[:2], readme=False)
    assert one["passes"] == {"hvd/value_and_grad/sync": 0,
                             "hvd/optimizer/sync": 1}
    assert not any(one["skipped"].values())
    assert sum(one["bytes"].values()) == one["tree_bytes"]


def test_remat_count_follows_the_layers(readme_step):
    """Under ``dots`` with flash attention the policy keeps the forward
    kernel's two named outputs in every layer: a count of the policy's
    answers (how often jax asks is its own business), so positive and
    twice as large for two layers as for one."""
    one = _step(jax.devices()[:2], num_layers=1)["saved"]
    assert readme_step["saved"][0] > 0
    assert readme_step["saved"] == [2 * one[0]]


@pytest.mark.parametrize("changes", [
    dict(remat_policy="full"), dict(remat=False), dict(attention="dense")],
    ids=["full", "no-remat", "dense"])
def test_remat_count_is_zero_where_nothing_named_is_kept(changes):
    assert _step(jax.devices()[:2], **changes)["saved"] == [0]


def test_remat_count_is_the_last_trace_not_a_sum(readme_step):
    """The same program traced again says the same, and one that keeps
    nothing (same program name) takes the count back to 0."""
    assert _step(jax.devices()[:2])["saved"] == readme_step["saved"]
    assert _step(jax.devices()[:2], remat=False)["saved"] == [0]


def test_manifest_says_how_much_of_the_causal_square_is_visited(
        readme_step):
    """A causal flash call notes, from shapes alone, the (Q tile, compute
    chunk) pairs of one head's forward that hold a visible pair and how
    many there are: what ``causal_tiles()`` counts for the tiles and the
    chunk the tile table gives this shape (T 128 is one tile here, the
    benchmark's T 1024 cuts its own into chunks)."""
    import importlib
    fa = importlib.import_module("horovod_tpu.ops.flash_attention")
    from horovod_tpu.ops import tile_table
    cfg = GPT2Config.tiny()
    bq, bk, _, _, chunk, _ = tile_table.lookup_full(
        cfg.d_model // cfg.num_heads, 128, cfg.dtype, "causal")
    visited, total = fa.causal_tiles(128, bq, bk, chunk)
    assert readme_step["causal_tiles"] == [[visited], [total]]
    assert 0 < visited <= total
    # dense attention runs no kernel and notes nothing new: the gauge keeps
    # what the program's last flash trace said
    dense = _step(jax.devices()[:2], attention="dense")
    assert dense["causal_tiles"] == readme_step["causal_tiles"]


def test_manifest_says_how_many_kernels_the_flash_backward_is(readme_step,
                                                             bd_step):
    """The backward of a step's flash attention calls says what it lowered
    to: one kernel where the tile table's entry keeps the K tile resident
    with a loop of chunks (GPT-2 medium's attention shape, head 64 at T
    1024: the dK/dV kernel also sums dQ, and the compiler's default VMEM
    covers it), two where it cannot: a sequence of one tile, the
    block-diffusion mask."""
    assert readme_step["flash_bwd"] == [[2], [0]]       # T 128: no loop
    assert "flash_dq" in readme_step["text"]
    at_entry = _step(jax.devices()[:1], max_seq_len=1024, num_layers=1,
                     num_heads=1)
    assert at_entry["flash_bwd"] == [[1], [0]]
    assert "flash_dkv" in at_entry["text"]
    assert "flash_dq" not in at_entry["text"]
    assert _program_gauge("flash_bwd_kernels", "bd_step") == [2]
    assert _program_gauge("flash_bwd_vmem_bytes", "bd_step") == [0]


def test_manifest_counts_nothing_on_one_device():
    alone = _step(jax.devices()[:1])
    assert set(alone["bytes"]) == {"hvd/value_and_grad/sync",
                                   "hvd/optimizer/sync"}
    assert not any(alone["bytes"].values())
    assert not any(alone["passes"].values())
    assert not any(alone["buckets"].values())
    # the skip sees objects, not the wire: it engages on one device too
    assert alone["skipped"] == {"hvd/value_and_grad/sync": 0,
                                "hvd/optimizer/sync": 1}


def test_a_sync_outside_hvd_spmd_leaves_no_manifest():
    before = hvd.metrics.snapshot()["gauges"].get("grad_sync_bytes", [])
    mapped = jax.shard_map(
        lambda g: hvd.allreduce_gradients(g), mesh=hvd.mesh(),
        in_specs=P(), out_specs=P(), check_vma=False)
    jax.jit(mapped).lower(jnp.ones((4, 4)))
    after = hvd.metrics.snapshot()["gauges"].get("grad_sync_bytes", [])
    assert after == before


# ---------------------------------------------------------------------------
# the set-up ledger
# ---------------------------------------------------------------------------

def test_set_up_ledger_books_a_programs_phases_under_its_name():
    def foo(x):
        return hvd.allreduce(jnp.sin(x) * 2.0)

    def bar(x):
        return jnp.cos(x) * 3.0

    hvd.spmd(foo)(jnp.ones((8, 16)))
    jax.jit(bar)(jnp.ones((3, 5)))
    for phase in ("trace", "lower", "backend"):
        assert _counter("jax_compile_seconds_total", phase=phase,
                        fun="foo") > 0, phase
        assert _counter("jax_compile_total", phase=phase, fun="foo") >= 1
        assert _counter("jax_compile_total", phase=phase, fun="other") >= 1
    # a function the package did not build has no series of its own
    assert _counter("jax_compile_total", fun="bar") == 0


def test_cache_load_is_taken_out_of_the_backend_seconds():
    tracing.note_program("baz")
    event = "/jax/compilation_cache/cache_retrieval_time_sec"
    tracing._on_jax_duration(event, 2.0)
    tracing._on_jax_duration("/jax/core/compile/backend_compile_duration",
                             2.5, fun_name="jit(baz)")
    tracing._on_jax_duration("/jax/core/compile/backend_compile_duration",
                             1.0, fun_name="jit(baz)")
    tracing._on_jax_duration("/jax/some/other_duration", 9.0)
    assert _counter("jax_compile_seconds_total", phase="cache_load",
                    fun="baz") == 2.0
    assert _counter("jax_compile_seconds_total", phase="backend",
                    fun="baz") == 1.5
    assert _counter("jax_compile_total", phase="backend", fun="baz") == 2
    assert _counter("jax_compile_total", phase="cache_load", fun="baz") == 1


def test_import_and_init_seconds_are_stamped():
    import importlib
    importlib.reload(hvd)       # the gauge is set when the package loads
    hvd.init()
    snap = hvd.metrics.snapshot()
    assert snap["gauges"]["import_seconds"][0]["value"] > 0
    assert snap["histograms"]["init_seconds"][0]["count"] >= 1


# ---------------------------------------------------------------------------
# spans on the profiler's host plane
# ---------------------------------------------------------------------------

def _host_spans(trace_dir):
    """[(name, start_ns, end_ns)] of the hvd: spans of a profiler trace."""
    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                spans += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                          for e in line.events
                          if e.name.startswith(tracing.SPAN_PREFIX)]
    return sorted(spans, key=lambda s: s[1])


@pytest.fixture(scope="module")
def traced_step(tmp_path_factory):
    """One ``step_once`` of a warm tiny engine, and one eager collective,
    under a profiler session."""
    from horovod_tpu.serving import InferenceEngine
    cfg = GPT2Config.tiny(dtype=jnp.float32)
    model = GPT2(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.ones((1, 4), jnp.int32))["params"]
    eng = InferenceEngine(model, params, slots=2, max_len=32, block_size=4,
                          prefill_chunk=1, name="spans")
    req = eng.submit(prompt=[1, 2, 3], max_new_tokens=8)
    for _ in range(4):                      # compile, and start generating
        eng.step_once()
    out = str(tmp_path_factory.mktemp("trace"))
    step, tokens = eng.step_count, len(req.tokens)
    with jax.profiler.trace(out):
        advanced = eng.step_once()
        hvd.allreduce(np.ones((hvd.size(), 4), np.float32), name="probe")
    assert advanced == 1 and len(req.tokens) == tokens + 1
    assert eng.decode_compiles == 1
    return {"spans": _host_spans(out), "step": step, "engine": eng}


def test_step_once_leaves_its_phases_inside_one_step_span(traced_step):
    spans = traced_step["spans"]
    steps = [s for s in spans if s[0] == "hvd:engine.step"]
    assert len(steps) == 1
    _, lo, hi = steps[0]
    inside = [s for s in spans
              if s[0].startswith("hvd:engine.") and s is not steps[0]]
    assert all(lo <= a and b <= hi for _, a, b in inside)
    first_seen = list(dict.fromkeys(n for n, _, _ in inside))
    assert first_seen == ["hvd:engine." + p for p in ENGINE_PHASES]
    # no two phases overlap: the enclosing span is the cause, not a sibling
    for (_, _, end), (_, start, _) in zip(inside, inside[1:]):
        assert end <= start


def test_an_eager_collective_is_one_collective_span(traced_step):
    names = [n for n, _, _ in traced_step["spans"]]
    assert names.count("hvd:collective") == 1


@pytest.mark.parametrize("phase", ENGINE_PHASES)
def test_each_engine_phase_has_its_counter_pair(traced_step, phase):
    assert traced_step["engine"].step_count > traced_step["step"]
    seconds = _counter("serve_step_phase_seconds_total", engine="spans",
                       phase=phase)
    count = _counter("serve_step_phase_total", engine="spans", phase=phase)
    assert seconds > 0 and count >= 1


# ---------------------------------------------------------------------------
# the block-diffusion decoder's names
# ---------------------------------------------------------------------------

def _lowered(step, program, *args):
    """A family's step lowered: its text, and its scope table with the
    compiled text that was read from."""
    text = step.lower(*args).as_text(debug_info=True)
    table, compiled = _asked(program)
    return {"text": text, "table": table, "compiled": compiled}


@pytest.fixture(scope="module")
def bd_step():
    from horovod_tpu.models import sdar
    hvd.init(devices=jax.devices()[:1])
    try:
        cfg = sdar.SDARConfig.tiny(experts_held=(2, 2), top_k=4,
                                   attention="flash", remat=True,
                                   flash_blocks=(16, 32))
        model = sdar.SDAR(cfg)
        tokens = jnp.zeros((2, 32), jnp.int32)
        params = model.init(jax.random.PRNGKey(0), tokens, tokens)["params"]
        opt = hvd.DistributedOptimizer(optax.sgd(0.1))

        def bd_step(params, opt_state, tokens):
            noise = sdar.block_noise(
                jax.random.split(jax.random.PRNGKey(1), tokens.shape[0]),
                tokens.shape[1], cfg.block_len)
            loss, grads = hvd.value_and_grad(
                lambda p: sdar.loss_fn(model, p, tokens, noise))(params)
            updates, opt_state = opt.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state, loss

        step = hvd.spmd(bd_step, in_specs=(P(), P(), P("hvd")),
                        out_specs=(P(), P(), P()))
        return _lowered(step, "bd_step", params, opt.init(params), tokens)
    finally:
        hvd.shutdown()
        hvd.init()          # back onto the session's 8 CPU devices


def test_lowered_block_diffusion_step_carries_its_scopes_and_manifest(
        bd_step):
    """The step of the second model family, lowered: its four scopes and
    the three kernel names in the text, and the routing manifest published
    under the program's name when the trace ends."""
    for name in SDAR_SCOPES + KERNELS:
        assert name in bd_step["text"], name
    read = {name: _program_gauge(name, "bd_step")
            for name in tracing._ROUTING}
    assert read["moe_rows_bound"] == [2 * 64 * 2]
    assert 0 < read["bd_tiles_visited"][0] < read["bd_tiles_total"][0]


# ---------------------------------------------------------------------------
# the hybrid conv/attention decoder's names
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def hybrid_step():
    from horovod_tpu.models import lfm2
    hvd.init(devices=jax.devices()[:1])
    try:
        cfg = lfm2.LFM2Config.tiny(experts_held=(2, 2), top_k=4,
                                   attention="flash", remat=True,
                                   flash_blocks=(16, 16))
        model = lfm2.LFM2(cfg)
        tokens = jnp.zeros((2, 32), jnp.int32)
        params = model.init(jax.random.PRNGKey(0), tokens)["params"]
        bias = np.full((cfg.num_layers, cfg.experts_total), 0.1, np.float32)
        opt = hvd.DistributedOptimizer(optax.sgd(0.1))

        def hybrid_step(params, opt_state, tokens):
            loss, grads = hvd.value_and_grad(
                lambda p: lfm2.loss_fn(model, p, tokens, bias))(params)
            updates, opt_state = opt.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state, loss

        step = hvd.spmd(hybrid_step, in_specs=(P(), P(), P("hvd")),
                        out_specs=(P(), P(), P()))
        return _lowered(step, "hybrid_step", params, opt.init(params),
                        tokens)
    finally:
        hvd.shutdown()
        hvd.init()          # back onto the session's 8 CPU devices


def test_lowered_hybrid_step_carries_its_scopes_and_manifest(hybrid_step):
    """The step of the third model family, lowered: its six scopes (two
    of them the expert layer's own) and the three kernel names in the text,
    and the routing manifest published under the program's name: the rows
    the routed layers are shaped for and the causal tiles of its one
    attention layer."""
    for name in LFM2_SCOPES + KERNELS:
        assert name in hybrid_step["text"], name
    read = {name: _program_gauge(name, "hybrid_step")
            for name in tracing._ROUTING}
    assert read["moe_rows_bound"] == [2 * 32 * 2]
    assert 0 < read["causal_tiles_visited"][0] < read["causal_tiles_total"][0]
    assert read["bd_tiles_total"] == []


@pytest.fixture(scope="module")
def latent_step():
    from horovod_tpu.models import glm4_moe_lite as glm
    hvd.init(devices=jax.devices()[:1])
    try:
        cfg = glm.Glm4MoeLiteConfig.tiny(experts_held=(2, 2), top_k=4,
                                         attention="flash", remat=True,
                                         flash_blocks=(16, 16))
        model = glm.Glm4MoeLite(cfg)
        tokens = jnp.zeros((2, 32), jnp.int32)
        params = model.init(jax.random.PRNGKey(0), tokens)["params"]
        bias = np.full((cfg.num_layers + 1, cfg.experts_total), 0.1,
                       np.float32)
        opt = hvd.DistributedOptimizer(optax.sgd(0.1))

        def latent_step(params, opt_state, tokens):
            loss, grads = hvd.value_and_grad(
                lambda p: glm.loss_fn(model, p, tokens, bias))(params)
            updates, opt_state = opt.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state, loss

        step = hvd.spmd(latent_step, in_specs=(P(), P(), P("hvd")),
                        out_specs=(P(), P(), P()))
        return _lowered(step, "latent_step", params, opt.init(params),
                        tokens)
    finally:
        hvd.shutdown()
        hvd.init()          # back onto the session's 8 CPU devices


def test_lowered_latent_attention_step_carries_its_scopes_and_manifest(
        latent_step):
    """The step of the fourth model family, lowered: its nine scopes (two
    of them the expert layer's own) and the three kernel names in the text,
    and the manifest published under the program's name: the rows the
    routed layers are shaped for, the causal tiles, what latent attention
    writes as expanded keys and values and the latent it expands, and that
    the multi-token-prediction module is in the step."""
    for name in GLM4_SCOPES + KERNELS:
        assert name in latent_step["text"], name
    read = {name: _program_gauge(name, "latent_step")
            for name in tracing._ROUTING}
    assert read["moe_rows_bound"] == [2 * 32 * 2]
    assert 0 < read["causal_tiles_visited"][0] < read["causal_tiles_total"][0]
    assert read["bd_tiles_total"] == []
    # 64 positions x 4 attention layers (3 blocks and the module's) in bf16
    assert read["mla_kv_expanded_bytes"] == [64 * 4 * 4 * (16 + 16) * 2]
    assert read["mla_latent_bytes"] == [64 * 4 * (16 + 4) * 2]
    assert read["mtp_modules"] == [1]


@pytest.fixture(scope="module")
def window_step():
    from horovod_tpu.models import smallthinker as st
    hvd.init(devices=jax.devices()[:1])
    try:
        cfg = st.SmallThinkerConfig.tiny(experts_held=(2, 2),
                                         attention="flash", remat=True,
                                         remat_policy="dots",
                                         flash_blocks=(8, 8))
        model = st.SmallThinker(cfg)
        tokens = jnp.zeros((2, 32), jnp.int32)
        params = model.init(jax.random.PRNGKey(0), tokens)["params"]
        opt = hvd.DistributedOptimizer(optax.sgd(0.1))

        def window_step(params, opt_state, tokens):
            loss, grads = hvd.value_and_grad(
                lambda p: st.loss_fn(model, p, tokens))(params)
            updates, opt_state = opt.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state, loss

        step = hvd.spmd(window_step, in_specs=(P(), P(), P("hvd")),
                        out_specs=(P(), P(), P()))
        return _lowered(step, "window_step", params, opt.init(params),
                        tokens)
    finally:
        hvd.shutdown()
        hvd.init()          # back onto the session's 8 CPU devices


def test_lowered_window_attention_step_carries_its_scopes_and_manifest(
        window_step):
    """The step of the fifth model family, lowered: its six scopes (two of
    them the expert layer's own, the early route among them) and the three
    kernel names in the text, and the manifest published under the
    program's name: the rows the routed layers are shaped for, the tiles of
    the global layer's causal call and of the window layers' calls, fewer
    of the same total."""
    for name in SMALLTHINKER_SCOPES + KERNELS:
        assert name in window_step["text"], name
    read = {name: _program_gauge(name, "window_step")
            for name in tracing._ROUTING}
    assert read["moe_rows_bound"] == [2 * 32 * 2]
    assert (0 < read["window_tiles_visited"][0]
            < read["causal_tiles_visited"][0]
            < read["causal_tiles_total"][0] == read["window_tiles_total"][0])
    assert read["bd_tiles_total"] == [] and read["mtp_modules"] == []
    assert read["flash_bwd_kernels"] == [2]
    # the route made before attention lies in the block and in no attention
    # scope; the kernels of a window layer lie in its scope
    table = window_step["table"]
    assert any(row.scopes == ("smallthinker/block", "moe/route")
               for row in table.values())
    for kind in ("attn_global", "attn_window"):
        assert any(row.scopes[:2] == ("smallthinker/block",
                                      "smallthinker/" + kind)
                   and row.scopes[-1] == "flash_attention"
                   for row in table.values()), kind
    assert not any("moe/route" in row.scopes
                   and any("attn" in s for s in row.scopes)
                   for row in table.values())


@pytest.mark.parametrize("gauges,want", [
    ({"window_tiles_visited": 252, "window_tiles_total": 1024}, 24.609375),
    ({"window_tiles_visited": 1024, "window_tiles_total": 1024}, 100.0),
    ({}, None),                 # the parent of PR 37 has no such series
], ids=["252-of-1024", "whole-square", "parent"])
def test_window_tiles_visited_share_reads_the_manifest(monkeypatch, gauges,
                                                       want):
    """``window_tiles_visited_share.train`` is data for the reader the
    benchmark has (``named:series_total``): ``train_step``'s two gauges as
    a percentage, and nothing (no raise) where the program does not publish
    them. Its four siblings are entered beside it, each with its file, each
    for the one cell; the scope's row names the metric that sums it."""
    spec, entry, named = _benchmark_metric("window_tiles_visited_share.train")
    monkeypatch.setattr(hvd.metrics, "snapshot", lambda: {
        "counters": {}, "histograms": {}, "gauges": {
            name: [{"labels": {"program": "train_step"}, "value": value},
                   {"labels": {"program": "eval_step"}, "value": 7}]
            for name, value in gauges.items()}})
    module, function = spec["reader"].split(":")
    assert (module, function) == ("named", "series_total")
    got = getattr(named, function)(None, **spec["args"])
    assert got == (want if want is None else pytest.approx(want))
    for sel in spec["args"]["series"] + spec["args"]["per"]:
        assert tracing.NAMES[sel["name"]].feeds == spec["name"]
    for name in ("window_tiles_visited_share.train", "mfu.train_smallthinker",
                 "swa_flash_roofline.train", "swa_flash_time_share.train",
                 "window_attn_ms.train"):
        other, its_entry, _ = _benchmark_metric(name)
        assert len(its_entry) == 1
        for key in ("unit", "layer", "moves", "source", "better"):
            assert other[key] == its_entry[0][key], (name, key)
        assert other["workloads"] == its_entry[0]["workloads"] == [
            "smallthinker21b-train-dp1"]
    attn, _, _ = _benchmark_metric("window_attn_ms.train")
    assert attn["reader"] == "scopes:scope_ms_per_run"
    assert attn["args"]["scopes"] == ["smallthinker/attn_window"]
    assert tracing.NAMES["smallthinker/attn_window"].feeds == attn["name"]
    assert attn["layer"] == tracing.NAMES["smallthinker/attn_window"].layer
    roofline, _, _ = _benchmark_metric("swa_flash_roofline.train")
    assert roofline["args"]["flops"] == "flops_smallthinker"
    assert sorted(roofline["args"]["contains"]) == sorted(KERNELS)


@pytest.mark.parametrize("gauge,want", [(2013265920, 2013.26592),
                                        (None, None)],
                         ids=["the-cell", "parent"])
def test_mla_kv_expanded_mb_reads_its_gauge(monkeypatch, gauge, want):
    """``mla_kv_expanded_mb.train`` is data for the reader the benchmark
    has (``named:series_total``): the gauge of ``train_step`` in MB, and
    nothing (no raise) where the program does not have it."""
    spec, entry, named = _benchmark_metric("mla_kv_expanded_mb.train")
    series = [] if gauge is None else [
        {"labels": {"program": "train_step"}, "value": gauge},
        {"labels": {"program": "eval_step"}, "value": 1}]
    monkeypatch.setattr(hvd.metrics, "snapshot", lambda: {
        "counters": {}, "histograms": {},
        "gauges": {"mla_kv_expanded_bytes": series} if series else {}})
    module, function = spec["reader"].split(":")
    assert module == "named"
    got = getattr(named, function)(None, **spec["args"])
    assert got == (want if want is None else pytest.approx(want))
    assert len(entry) == 1
    for key in ("unit", "layer", "moves", "source", "better", "workloads"):
        assert spec[key] == entry[0][key], key
    for sel in spec["args"]["series"]:
        assert tracing.NAMES[sel["name"]].feeds == spec["name"]
    # the step's whole share of the peak and the kernels' pair are entered
    # beside it, each with its file
    for name in ("mfu.train_glm4", "mla_flash_time_share.train",
                 "mla_flash_roofline.train"):
        other, its_entry, _ = _benchmark_metric(name)
        assert len(its_entry) == 1
        assert other["workloads"] == its_entry[0]["workloads"] == [
            "glm47f-train-dp1"]


@pytest.mark.parametrize("gauge,want", [(0.125, 12.5), (0.0, 0.0),
                                        (None, None)],
                         ids=["an-eighth", "a-bias-that-moves-nothing",
                              "parent"])
def test_moe_bias_moved_share_reads_its_gauge(monkeypatch, gauge, want):
    """``moe_bias_moved_share.train`` is data for the reader the benchmark
    has (``named:series_total``): the gauge of ``train_step`` in per cent,
    and nothing (no raise) where the program does not have it."""
    spec, entry, named = _benchmark_metric("moe_bias_moved_share.train")
    series = [] if gauge is None else [
        {"labels": {"program": "train_step"}, "value": gauge},
        {"labels": {"program": "eval_step"}, "value": 1}]
    monkeypatch.setattr(hvd.metrics, "snapshot", lambda: {
        "counters": {}, "histograms": {},
        "gauges": {"moe_bias_moved_share": series} if series else {}})
    module, function = spec["reader"].split(":")
    assert module == "named"
    got = getattr(named, function)(None, **spec["args"])
    assert got == (want if want is None else pytest.approx(want))
    assert len(entry) == 1
    for key in ("unit", "layer", "moves", "source", "better"):
        assert spec[key] == entry[0][key], key
    # the file's list is the entry's head (a later cell is appended to the
    # entry alone): PR 31's cell, then (PR 33) the latent-attention one
    assert spec["workloads"] == ["lfm2-24b-train-dp1"]
    assert entry[0]["workloads"] == spec["workloads"] + ["glm47f-train-dp1"]
    for sel in spec["args"]["series"]:
        assert tracing.NAMES[sel["name"]].feeds == spec["name"]


@pytest.mark.parametrize("gauges,want", [
    ({"moe_local_assignments": 16384, "moe_rows_tight": 32768}, 50.0),
    ({"moe_local_assignments": 40960, "moe_rows_tight": 32768}, 125.0),
    ({"moe_local_assignments": 16384}, None),   # the parent of PR 34
], ids=["half-a-window", "a-second-window", "parent"])
def test_moe_rows_filled_share_reads_the_manifest(monkeypatch, gauges, want):
    """``moe_rows_filled_share.train`` is data for the reader the benchmark
    has (``named:series_total``): the rows the check batch gave the experts
    held over the rows ``train_step``'s share is shaped for, in per cent,
    and nothing (no raise) where the program publishes no such shape."""
    spec, entry, named = _benchmark_metric("moe_rows_filled_share.train")
    monkeypatch.setattr(hvd.metrics, "snapshot", lambda: {
        "counters": {}, "histograms": {}, "gauges": {
            name: [{"labels": {"program": "train_step"}, "value": value},
                   {"labels": {"program": "eval_step"}, "value": 1}]
            for name, value in gauges.items()}})
    module, function = spec["reader"].split(":")
    assert module == "named"
    got = getattr(named, function)(None, **spec["args"])
    assert got == (want if want is None else pytest.approx(want))
    assert len(entry) == 1
    for key in ("unit", "layer", "moves", "source", "better"):
        assert spec[key] == entry[0][key], key
    # the file's list is the entry's head: a later cell (PR 37's) is
    # appended to the entry alone
    assert spec["workloads"] == ["sdar30b-bd-train-dp1", "lfm2-24b-train-dp1",
                                 "glm47f-train-dp1"]
    assert entry[0]["workloads"] == spec["workloads"] + [
        "smallthinker21b-train-dp1"]
    for sel in spec["args"]["per"]:
        assert tracing.NAMES[sel["name"]].feeds == spec["name"]


# ---------------------------------------------------------------------------
# the benchmark's reading of the causal manifest (PR 30)
# ---------------------------------------------------------------------------

def _benchmark_metric(name):
    root = os.path.dirname(PKG)
    with open(os.path.join(root, "benchmark", "layer_metrics",
                           name + ".json")) as f:
        spec = json.load(f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        entry = [m for m in json.load(f)["per_layer"] if m["name"] == name]
    sys.path.insert(0, os.path.join(root, "benchmark"))
    try:
        named = importlib.import_module("readers.named")
    finally:
        sys.path.pop(0)
    return spec, entry, named


@pytest.mark.parametrize("gauges,want", [
    ({"causal_tiles_visited": 10, "causal_tiles_total": 16}, 62.5),
    ({"causal_tiles_visited": 16, "causal_tiles_total": 16}, 100.0),
    ({}, None),                 # the parent of PR 30 has no such series
], ids=["10-of-16", "whole-square", "parent"])
def test_causal_tiles_visited_share_reads_the_manifest(monkeypatch, gauges,
                                                       want):
    """``causal_tiles_visited_share.train`` is data for the reader the
    benchmark has (``named:series_total``): the two gauges of
    ``train_step`` as a share, and nothing (no raise) where the program
    does not have them."""
    spec, entry, named = _benchmark_metric(
        "causal_tiles_visited_share.train")
    snapshot = {"counters": {}, "histograms": {}, "gauges": {
        name: [{"labels": {"program": "train_step"}, "value": value},
               {"labels": {"program": "eval_step"}, "value": 1}]
        for name, value in gauges.items()}}
    monkeypatch.setattr(hvd.metrics, "snapshot", lambda: snapshot)
    module, function = spec["reader"].split(":")
    assert module == "named"
    got = getattr(named, function)(None, **spec["args"])
    assert got == (want if want is None else pytest.approx(want))
    assert len(entry) == 1
    for key in ("unit", "layer", "moves", "source", "better"):
        assert spec[key] == entry[0][key], key
    # run.py goes by the entry's list; a later cell is appended there alone
    # (a PR may edit no file the benchmark has), so the file's list is its
    # head: the cells of PR 30, then (PR 31) the hybrid decoder's and
    # (PR 33) the latent-attention one's
    cells = entry[0]["workloads"]
    assert cells[:len(spec["workloads"])] == spec["workloads"]
    assert cells[len(spec["workloads"]):] == ["lfm2-24b-train-dp1",
                                              "glm47f-train-dp1",
                                              "smallthinker21b-train-dp1"]
    for sel in spec["args"]["series"] + spec["args"]["per"]:
        assert tracing.NAMES[sel["name"]].feeds == spec["name"]


@pytest.mark.parametrize("gauges,want", [
    ({"flash_bwd_kernels": 1, "flash_bwd_vmem_bytes": 52428800}, 1.0),
    ({"flash_bwd_kernels": 2, "flash_bwd_vmem_bytes": 0}, 2.0),
    ({}, None),                 # the parent of PR 36 has no such series
], ids=["one-kernel", "two-kernels", "parent"])
def test_flash_bwd_kernels_reads_the_manifest(monkeypatch, gauges, want):
    """``flash_bwd_kernels.train`` is data for the reader the benchmark has
    (``named:series_total``, no ``per``): ``train_step``'s gauge as it is,
    in all five training cells, and nothing (no raise) where the program
    does not publish it."""
    spec, entry, named = _benchmark_metric("flash_bwd_kernels.train")
    monkeypatch.setattr(hvd.metrics, "snapshot", lambda: {
        "counters": {}, "histograms": {}, "gauges": {
            name: [{"labels": {"program": "train_step"}, "value": value},
                   {"labels": {"program": "eval_step"}, "value": 7}]
            for name, value in gauges.items()}})
    module, function = spec["reader"].split(":")
    assert (module, function) == ("named", "series_total")
    assert "per" not in spec["args"]
    got = getattr(named, function)(None, **spec["args"])
    assert got == (want if want is None else pytest.approx(want))
    assert len(entry) == 1
    for key in ("unit", "layer", "moves", "source", "better"):
        assert spec[key] == entry[0][key], key
    assert (spec["unit"], spec["better"]) == ("kernels", "lower")
    assert spec["workloads"] == [
        "gpt2m-train-dp1", "gpt2m-train-dp4", "sdar30b-bd-train-dp1",
        "lfm2-24b-train-dp1", "glm47f-train-dp1"]
    assert entry[0]["workloads"] == spec["workloads"] + [
        "smallthinker21b-train-dp1"]
    for sel in spec["args"]["series"]:
        assert tracing.NAMES[sel["name"]].feeds == spec["name"]
    assert tracing.NAMES["flash_bwd_vmem_bytes"].feeds.startswith("registry")


# ---------------------------------------------------------------------------
# the scope table
# ---------------------------------------------------------------------------

def _rows_in(table, scope):
    return {name: row for name, row in table.items() if scope in row.scopes}


@pytest.fixture(scope="module")
def steps(readme_step, bd_step, hybrid_step, latent_step, window_step):
    # hvd/optimizer/sync lowers nothing on the README path; the slices of
    # hvd/fusion/unpack and the transposes of flash/layout are fused into
    # their consumers by the CPU's compiler and read as those (a fusion's
    # scope is its root's); the chip's compiler keeps them as copies, which
    # tests/test_aot_tpu_compile.py reads
    return {"readme": (readme_step, [n for n in TRAINER_SCOPES if n not in (
        "hvd/optimizer/sync", "hvd/fusion/unpack", "flash/layout")]),
            "block-diffusion": (bd_step, SDAR_SCOPES),
            "hybrid": (hybrid_step, LFM2_SCOPES),
            "latent": (latent_step, GLM4_SCOPES),
            "window": (window_step, SMALLTHINKER_SCOPES)}


@pytest.mark.parametrize("family", ["readme", "block-diffusion", "hybrid",
                                    "latent", "window"])
def test_scope_table_holds_every_scope_of_the_step(steps, family):
    """The table of a family's compiled step holds, in some row, every
    scope the lowered text carries, the flash kernels' wrapper among them;
    every row's scopes are rows of ``NAMES`` of kind scope, its layer the
    innermost one's; and each instruction is there once: the names are
    those of the instruction lines of the compiled text, none twice."""
    step, scopes = steps[family]
    table, compiled = step["table"], step["compiled"]
    for scope in scopes + ["flash_attention"]:
        assert _rows_in(table, scope), scope
    for name, row in table.items():
        for scope in row.scopes:
            assert tracing.NAMES[scope].kind == "scope", (name, scope)
        assert len(set(row.scopes)) == len(row.scopes)
        if row.scopes:
            assert row.layer == tracing.NAMES[row.scopes[-1]].layer
        assert row.direction in ("fwd", "remat", "bwd")
    defined = re.findall(r"^\s+(?:ROOT )?%?([\w.\-]+) = ", compiled, re.M)
    assert len(defined) == len(set(defined))
    assert set(table) <= set(defined)
    entry = compiled[compiled.index("\nENTRY "):]
    entry = entry[:entry.index("\n}")]
    assert set(re.findall(r"^\s+(?:ROOT )?%?([\w.\-]+) = ", entry, re.M)) \
        <= set(table)


def test_scope_table_keeps_nested_scopes_outermost_first(steps):
    """The module's block lies in ``glm4/mtp``, its attention in
    ``glm4/attn`` inside that, the kernel's wrapper inside that; the
    buckets are packed inside the sync that packs them. The module path
    flax puts before a scope (``h0/attn``, ``moe/moe/route``) is no scope."""
    latent = steps["latent"][0]["table"]
    assert any(row.scopes == ("glm4/mtp", "glm4/block", "glm4/attn",
                              "flash_attention") for row in latent.values())
    assert any(row.scopes == ("glm4/block", "moe/route")
               for row in latent.values())
    assert any(row.scopes[:1] == ("glm4/mtp",) and "moe/experts" in row.scopes
               for row in latent.values())
    readme = steps["readme"][0]["table"]
    assert any(row.scopes == ("hvd/value_and_grad/sync", "hvd/fusion/pack")
               for row in readme.values())
    assert any(row.scopes == ("gpt2/attn", "flash_attention")
               for row in readme.values())


@pytest.mark.parametrize("policy,reruns", [("dots", False), ("full", True)])
def test_scope_table_tells_backward_from_rerun(readme_step, policy, reruns):
    """The rule the compiled text supports: ``rematted_computation`` marks
    the forward that ``jax.checkpoint`` runs again (it lies inside
    ``transpose(`` too, so that cannot tell them apart), ``transpose(``
    without it the backward, and the rest is forward: the update and the
    sync are not differentiated. Under ``full`` the kernel's wrapper is
    run again; under ``dots`` its output is kept and it is not."""
    step = readme_step if policy == "dots" else _step(
        jax.devices()[:2], table=True, remat_policy="full")
    table = step["table"]
    for row in table.values():
        again = "rematted_computation" in row.op_name
        assert (row.direction == "remat") == again
        assert (row.direction == "bwd") == (
            "transpose(" in row.op_name and not again)
        if again:
            assert "transpose(" in row.op_name
    for scope in ("gpt2/loss_head", "gpt2/attn", "gpt2/mlp", "gpt2/lm_head"):
        assert {"fwd", "bwd"} <= {r.direction for r in
                                  _rows_in(table, scope).values()}, scope
    for scope in ("hvd/optimizer/update", "hvd/value_and_grad/sync"):
        assert {r.direction for r in
                _rows_in(table, scope).values()} == {"fwd"}, scope
    rerun = {r.direction for r in _rows_in(table, "flash_attention").values()}
    assert ("remat" in rerun) == reruns
    assert "remat" in {r.direction for r in
                       _rows_in(table, "gpt2/mlp").values()}


def test_scope_table_marks_a_loop_and_lists_its_body(readme_step):
    """On the CPU the interpreted kernel is a ``while``: a container, whose
    device event would cover its body's; the body's instructions are rows
    of their own, in the kernel's scope, and are no containers."""
    table = readme_step["table"]
    loops = {n: r for n, r in table.items() if r.container}
    assert loops and all(n.startswith(("while", "conditional", "call"))
                         for n in loops)
    assert any("flash_attention" in r.scopes for r in loops.values())
    body = [r for r in table.values()
            if "/while/body/" in r.op_name and not r.container]
    assert body and all("flash_attention" in r.scopes for r in body)


# what the chip's compiler writes (lines of an AOT compile for a v5e, cut
# down): a kernel with its wrapper's metadata, XLA's grouped kernel with
# none, a fusion in the expert layer's backward, a loop over windows whose
# body and condition are computations of their own, a fusion's inside,
# which never runs as an event
CHIP_TEXT = '''HloModule jit_train_step, is_scheduled=true

%fused_computation.7 (param_0.1: f32[8,4]) -> f32[8,4] {
  %param_0.1 = f32[8,4]{1,0} parameter(0)
  ROOT %multiply.3 = f32[8,4]{1,0} multiply(%param_0.1, %param_0.1), metadata={op_name="jit(train_step)/transpose(jvp(LFM2))/h1/moe/moe/experts/mul"}
}

%wide.region_2.16 (wide.param: (s32[], f32[8,4])) -> (s32[], f32[8,4]) {
  %wide.param = (s32[]{:T(128)}, f32[8,4]{1,0:T(8,128)}) parameter(0)
  %ragged-dot-none.3 = f32[8,4]{1,0:T(8,128)} custom-call(%wide.param), custom_call_target="tpu_custom_call", backend_config={"x":{"y":1}}
  %gather_fusion.1 = f32[8,4]{1,0:T(8,128)} fusion(%ragged-dot-none.3), kind=kLoop, calls=%fused_computation.7, metadata={op_name="jit(train_step)/jvp(LFM2)/h1/moe/moe/experts/while/body/gather" stack_frame_id=3}
  ROOT %tuple.9 = (s32[]{:T(128)}, f32[8,4]{1,0:T(8,128)}) tuple(%wide.param, %gather_fusion.1)
}

%wide.region_3.17 (wide.param.1: (s32[], f32[8,4])) -> pred[] {
  %wide.param.1 = (s32[]{:T(128)}, f32[8,4]{1,0:T(8,128)}) parameter(0)
  ROOT %lt.5 = pred[]{:T(512)} compare(%wide.param.1, %wide.param.1), direction=LT, metadata={op_name="jit(train_step)/jvp(LFM2)/h1/moe/moe/experts/while/cond/lt"}
}

ENTRY %main.42 (tokens.1: s32[1,8]) -> (f32[8,4], bf16[8,512,64]) {
  %tokens.1 = s32[1,8]{1,0:T(1,128)} parameter(0), metadata={op_name="tokens"}
  %copy-start.2 = (s32[1,8]{1,0:T(1,128)S(1)}, s32[1,8]{1,0:T(1,128)}, u32[]{:S(2)}) copy-start(%tokens.1)
  %flash_fwd.2 = (bf16[8,512,64]{2,1,0:T(8,128)(2,1)S(1)}, f32[8,512,1]{2,1,0:T(8,128)S(1)}) custom-call(%copy-start.2), custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={}}, metadata={op_name="jit(train_step)/jvp(LFM2)/h2/lfm2/attn/attn/flash_attention/flash_fwd/pallas_call" stack_frame_id=1}
  %flash_dq.7 = bf16[8,512,64]{2,1,0:T(8,128)(2,1)S(1)} custom-call(%flash_fwd.2), custom_call_target="tpu_custom_call"
  %while.4 = (s32[]{:T(128)}, /*index=1*/f32[8,4]{1,0:T(8,128)}) while(%flash_fwd.2), condition=%wide.region_3.17, body=%wide.region_2.16, metadata={op_name="jit(train_step)/jvp(LFM2)/h1/moe/moe/experts/while" stack_frame_id=3}
  %fusion.11 = f32[8,4]{1,0:T(8,128)} fusion(%while.4), kind=kLoop, calls=%fused_computation.7, metadata={op_name="jit(train_step)/transpose(jvp(LFM2))/jvp(LFM2)/checkpoint/rematted_computation/h0/lfm2/shortconv/conv/mul" stack_frame_id=9}
  %all-reduce.1 = f32[8,4]{1,0:T(8,128)} all-reduce(%fusion.11), replica_groups={{0,1,2,3}}, to_apply=%fused_computation.7, metadata={op_name="jit(train_step)/shard_map/hvd/value_and_grad/sync/psum"}
  ROOT %tuple.1 = (f32[8,4]{1,0:T(8,128)}, bf16[8,512,64]{2,1,0:T(8,128)(2,1)S(1)}) tuple(%all-reduce.1, %flash_fwd.2)
}
'''


def test_scope_table_of_the_chips_text_leaves_no_kernel_unscoped():
    """A kernel is never unscoped: the flash kernel lies in its wrapper's
    scope and XLA's grouped kernel, whose custom calls carry no metadata,
    has the expert layer through its row ``ragged-dot``; a flash kernel
    that lost its metadata would still have the kernels' layer. What runs
    inside a fusion or reduces for a collective is no row; what a loop
    runs is."""
    table = tracing._read_scopes(CHIP_TEXT)
    assert set(table) == {
        "tokens.1", "copy-start.2", "flash_fwd.2", "flash_dq.7", "while.4",
        "fusion.11", "all-reduce.1", "tuple.1", "wide.param",
        "ragged-dot-none.3", "gather_fusion.1", "tuple.9", "wide.param.1",
        "lt.5"}
    products = table["ragged-dot-none.3"]
    assert products.kernel == "ragged-dot" and products.scopes == ()
    assert products.layer == tracing.NAMES["moe/experts"].layer
    assert products.op_name == "" and not products.container
    fwd = table["flash_fwd.2"]
    assert fwd.kernel == "flash_fwd" and fwd.direction == "fwd"
    assert fwd.scopes == ("lfm2/attn", "flash_attention")
    assert fwd.layer == tracing.NAMES["flash_attention"].layer
    bare = table["flash_dq.7"]
    assert bare.scopes == () and bare.kernel == "flash_dq"
    assert bare.layer == tracing.NAMES["flash_dq"].layer
    assert table["while.4"].container
    assert table["while.4"].scopes == ("moe/experts",)
    inside = table["gather_fusion.1"]
    assert inside.scopes == ("moe/experts",) and not inside.container
    assert table["fusion.11"].direction == "remat"
    assert table["fusion.11"].scopes == ("lfm2/shortconv",)
    # no name of ours: the copy the compiler put in, the parameter
    for name in ("copy-start.2", "tokens.1", "tuple.1"):
        assert table[name].layer is None and table[name].kernel is None
    # the table's own names for kernels are NAMES rows of kind kernel
    assert {r.kernel for r in table.values()} - {None} <= {
        n for n, row in tracing.NAMES.items() if row.kind == "kernel"}


def _registry_of(program):
    """Every series of the set-up ledger, and every gauge labelled with
    ``program``."""
    snap = hvd.metrics.snapshot()
    ledger = {name: sorted((sorted(s["labels"].items()), s["value"])
                           for s in snap["counters"].get(name, ()))
              for name in ("jax_compile_seconds_total", "jax_compile_total")}
    gauges = {name: [s["value"] for s in series
                     if s["labels"].get("program") == program]
              for name, series in snap["gauges"].items()}
    return ledger, {k: v for k, v in gauges.items() if v}


def test_asking_for_the_table_leaves_the_step_and_the_registry_alone():
    """Asking lowers and compiles the program once more: the set-up ledger
    books none of it, no manifest gauge of the program moves, and the step
    is the bare jit before and after: it donates what it was told to,
    ``lower`` works, and a second asking costs nothing."""
    hvd.init(devices=jax.devices()[:2])
    try:
        opt = hvd.DistributedOptimizer(optax.sgd(0.1))

        def asked_step(params, opt_state, x):
            ran.append(1)
            loss, grads = hvd.value_and_grad(
                lambda p: jnp.mean((x @ p["w"]) ** 2))(params)
            updates, opt_state = opt.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state, loss

        step = hvd.spmd(asked_step, in_specs=(P(), P(), P("hvd")),
                        out_specs=(P(), P(), P()), donate_argnums=(0, 1))
        assert type(step) is type(jax.jit(lambda: 0))
        assert tracing.scope_table("asked_step") is None    # never traced
        ran = []
        place = lambda tree, spec: jax.device_put(
            tree, jax.sharding.NamedSharding(hvd.mesh(), spec))
        params = place({"w": jnp.ones((4, 3))}, P())
        state = place(opt.init(params), P())
        x = place(jnp.ones((8, 4)), P("hvd"))
        params1, state1, loss1 = step(params, state, x)
        assert params["w"].is_deleted()
        before = _registry_of("asked_step")
        assert sorted(before[1]["grad_sync_passes"]) == [0, 1]
        table = tracing.scope_table("asked_step")
        assert _rows_in(table, "hvd/value_and_grad/sync")
        assert _registry_of("asked_step") == before
        assert len(ran) == 1        # jit's trace cache answered
        assert tracing.scope_table("asked_step") is table
        params2, state2, loss2 = step(params1, state1, x)
        assert params1["w"].is_deleted() and not params2["w"].is_deleted()
        assert float(loss2) < float(loss1)
        assert _registry_of("asked_step") == before
        assert "asked_step" in step.lower(params2, state2, x).as_text()
    finally:
        hvd.init()          # back onto the session's 8 CPU devices


def test_asking_about_a_step_that_ran_on_unplaced_arrays():
    """Arrays that were never placed give jit no sharding, the noted shapes
    do: the first lowering misses jit's trace cache (the function's Python
    runs once more, in silence), the second, without shardings, is the
    program that ran. The ledger and the gauges stand either way."""
    ran = []

    def unplaced_step(w, x):
        ran.append(1)
        return hvd.allreduce_gradients({"w": w * jnp.mean(x)})["w"]

    step = hvd.spmd(unplaced_step, in_specs=(P(), P("hvd")), out_specs=P())
    step(np.ones((4, 3), np.float32), np.ones((16, 4), np.float32))
    before = _registry_of("unplaced_step")
    table = tracing.scope_table("unplaced_step")
    assert len(ran) == 2
    assert any("unplaced_step" in row.op_name for row in table.values())
    assert _registry_of("unplaced_step") == before
    assert tracing.scope_table("unplaced_step") is table and len(ran) == 2


def test_scope_table_of_an_unknown_or_dead_program_is_none():
    import gc
    assert tracing.scope_table("no_such_program") is None

    def short_lived(x):
        return x * 2.0

    step = hvd.spmd(short_lived)
    assert tracing.scope_table("short_lived") is None       # never traced
    step.lower(jnp.ones((8, 4)))
    del step
    gc.collect()
    assert tracing.scope_table("short_lived") is None       # and now gone

    def with_a_static(x, n):
        return x * n

    hvd.spmd(with_a_static, static_argnums=(1,)).lower(jnp.ones((8, 4)), 2)
    assert tracing.scope_table("with_a_static") is None


def _scope_reader():
    root = os.path.dirname(PKG)
    sys.path.insert(0, os.path.join(root, "benchmark"))
    try:
        return importlib.import_module("readers.scopes")
    finally:
        sys.path.pop(0)


def _event(name, start, ns):
    return (f"%{name} = f32[8,128]{{1,0:T(8,128)}} fusion(f32[8,128] %p.1)",
            start, ns)


@pytest.fixture()
def joined_window(readme_step, monkeypatch):
    """Two whole 1,000 ns runs of ``jit_train_step`` whose events are named
    from the README step's real table: a loop of the kernel's wrapper over
    two instructions of its body, one instruction of the loss head's
    backward, one of the update, one in no scope, one name the table does
    not hold."""
    table = readme_step["table"]
    pick = lambda test: next(n for n, r in sorted(table.items()) if test(r))
    names = {
        "loop": pick(lambda r: r.container and "flash_attention" in r.scopes),
        "body": pick(lambda r: not r.container and "/while/body/" in r.op_name
                     and "flash_attention" in r.scopes),
        "head": pick(lambda r: r.scopes == ("gpt2/loss_head",)
                     and r.direction == "bwd" and not r.container),
        "update": pick(lambda r: r.scopes == ("hvd/optimizer/update",)),
        "pack": pick(lambda r: "hvd/fusion/pack" in r.scopes),
        "loose": pick(lambda r: r.layer is None and not r.container),
    }
    ops = []
    for run in (1000, 2000):
        ops += [_event(names["loop"], run, 400),        # covers its body
                _event(names["body"], run + 10, 150),
                _event(names["body"], run + 200, 150),
                _event(names["head"], run + 400, 100),
                _event(names["update"], run + 500, 200),
                _event(names["pack"], run + 700, 50),
                _event(names["loose"], run + 750, 30),
                _event("fusion.99999", run + 800, 20)]
    win = {"modules": [("jit_train_step(1)", 1000, 1000),
                       ("jit_train_step(1)", 2000, 1000)],
           "ops": ops, "asyncs": []}
    monkeypatch.setattr(tracing, "scope_table",
                        lambda program: table if program == "train_step"
                        else None)
    from types import SimpleNamespace
    return SimpleNamespace(win=win), names


@pytest.mark.parametrize("args,want", [
    (dict(scopes=["flash_attention"]), 300e-6),
    (dict(scopes=["gpt2/loss_head"]), 100e-6),
    (dict(scopes=["gpt2/loss_head", "hvd/optimizer/update"]), 300e-6),
    (dict(scopes=["hvd/fusion/pack", "hvd/value_and_grad/sync"],
          collectives=False), 50e-6),
    (dict(unscoped=True), 50e-6),
    (dict(unscoped=True, share=True), 100 * 50 / 800),
    (dict(scopes=["moe/experts"]), None),       # not in this program
], ids=["loop-skipped-body-counted", "one-scope", "any-of-two",
        "nested-in-the-sync", "unscoped-with-the-unknown", "share-of-busy",
        "a-scope-the-program-lacks"])
def test_scope_reader_sums_by_hand(joined_window, capsys, args, want):
    """``readers/scopes.scope_ms_per_run`` over a synthetic window: the sums
    by hand in ms a run, the container skipped, the unknown name counted as
    unscoped and its share printed; busy is 800 ns a run (the loop's 400 and
    the 400 after it), of which the lines hold 700 (the loop's body is 300)."""
    r, _ = joined_window
    got = _scope_reader().scope_ms_per_run(r, "train_step", **args)
    assert got == (want if want is None else pytest.approx(want))
    printed = capsys.readouterr().out
    assert "2 whole runs" in printed
    assert "did not hold: 2.500 %" in printed       # 20 of 800
    assert "87.50 % of the busy time" in printed    # 700 of 800
    # the second reading of a run prints nothing more
    _scope_reader().scope_ms_per_run(r, "train_step", **args)
    assert capsys.readouterr().out == ""


def test_scope_reader_leaves_out_what_it_is_told_to(joined_window):
    r, names = joined_window
    read = _scope_reader().scope_ms_per_run
    whole = read(r, "train_step", scopes=["flash_attention"])
    assert read(r, "train_step", scopes=["flash_attention"],
                exclude_contains=[names["body"]]) == 0.0 != whole


@pytest.mark.parametrize("program", ["absent", "older"])
def test_scope_reader_reads_nothing_without_a_table(joined_window,
                                                    monkeypatch, program):
    """A program that was never traced under that name, and a package that
    has no ``scope_table`` at all (the parent commit): None, no raise."""
    r, _ = joined_window
    if program == "older":
        monkeypatch.delattr(tracing, "scope_table")
    read = _scope_reader().scope_ms_per_run
    assert read(r, "eval_step" if program == "absent" else "train_step",
                unscoped=True, share=True) is None
    from types import SimpleNamespace
    assert read(SimpleNamespace(win=None), "train_step", unscoped=True) is None


SCOPE_METRICS = ["unscoped_time_share.train", "grad_sync_local_ms.train",
                 "flash_layout_ms.train",
                 "moe_experts_outside_products_ms.train",
                 "loss_head_ms.train", "loss_head_ms.train_glm4",
                 "mla_expand_ms.train", "shortconv_ms.train"]


@pytest.mark.parametrize("name", SCOPE_METRICS)
def test_scope_metric_file_names_its_entry_and_its_scopes(name):
    """Each of the eight metrics is data for ``scopes:scope_ms_per_run``:
    its file and its ``BENCHMARK.json`` entry say the same, the scopes it
    sums are rows of ``NAMES`` of kind scope, and the row of each scope that
    has a metric of its own names it."""
    spec, entry, _ = _benchmark_metric(name)
    assert spec["reader"] == "scopes:scope_ms_per_run"
    assert len(entry) == 1
    for key in ("unit", "layer", "moves", "source", "better"):
        assert spec[key] == entry[0][key], key
    # the file's list is the entry's head; the four that every training cell
    # reports had PR 37's cell appended to the entry alone
    later = (["smallthinker21b-train-dp1"] if "glm4" not in name
             and len(spec["workloads"]) > 2 else [])
    assert entry[0]["workloads"] == spec["workloads"] + later
    assert entry[0]["source"] == "device_trace"
    assert spec["args"]["module"] == "train_step"
    for scope in spec["args"].get("scopes", ()):
        assert tracing.NAMES[scope].kind == "scope"
        assert name in tracing.NAMES[scope].feeds, scope
    assert bool(spec["args"].get("unscoped")) != bool(
        spec["args"].get("scopes"))
    import inspect
    accepted = inspect.signature(_scope_reader().scope_ms_per_run).parameters
    assert set(spec["args"]) <= set(accepted)
