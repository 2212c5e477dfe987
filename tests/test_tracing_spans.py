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
                  "hvd/fusion/unpack", "gpt2/loss_head"]
KERNELS = ["flash_fwd", "flash_dq", "flash_dkv"]
SDAR_SCOPES = ["sdar/attn", "moe/route", "moe/experts", "sdar/loss_head"]
LFM2_SCOPES = ["lfm2/shortconv", "lfm2/attn", "lfm2/dense_mlp", "moe/route",
               "moe/experts", "lfm2/loss_head"]
GLM4_SCOPES = ["glm4/mla_down", "glm4/mla_up", "glm4/attn", "glm4/dense_mlp",
               "glm4/shared_expert", "glm4/mtp", "glm4/loss_head",
               "moe/route", "moe/experts"]
ROUTING = ["moe_rows_bound", "moe_rows_tight", "moe_rows_overflow_layers",
           "bd_tiles_visited", "bd_tiles_total",
           "causal_tiles_visited", "causal_tiles_total",
           "mla_kv_expanded_bytes", "mla_latent_bytes", "mtp_modules",
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
    assert set(ROUTING) <= names
    assert {"engine." + p for p in ENGINE_PHASES} <= names
    # and the table lists nothing that is not emitted
    assert set(tracing.NAMES) - names == set(), set(tracing.NAMES) - names


def test_the_table_names_a_layer_and_a_reader_for_every_row():
    for name, row in tracing.NAMES.items():
        assert row.kind in ("span", "scope", "kernel", "counter", "gauge")
        assert row.layer and row.covers and row.feeds, name
        assert "\n" not in row.covers
    phases = [n for n in tracing.NAMES if n.startswith("engine.")]
    assert phases == ["engine.step"] + ["engine." + p for p in ENGINE_PHASES]


def test_the_profiler_knob_is_gone():
    from horovod_tpu import confbus, config
    assert not hasattr(config.Config(), "trace_jax_profiler")
    assert "HOROVOD_TRACE_JAX_PROFILER" not in confbus._IMMUTABLE_FIELDS


# ---------------------------------------------------------------------------
# the README step, lowered
# ---------------------------------------------------------------------------

def _step(devices, readme=True, touch=False, **changes):
    """The README train step (or the same with plain jax.value_and_grad)
    lowered on ``devices``: its text, its program name, the manifest it
    left, and the bytes of its parameter tree. ``touch`` multiplies the
    gradients by one between the two syncs: new objects, so that both
    passes lower, as they did before a pass could be skipped. ``changes``
    are to the model's configuration (flash attention under
    ``remat=dots``, two layers)."""
    hvd.init(devices=devices)
    try:
        cfg = dataclasses.replace(
            GPT2Config.tiny(attention="flash", remat=True,
                            remat_policy="dots"), **changes)
        model = GPT2(cfg)
        tokens = jnp.zeros((2 * len(devices), 128), jnp.int32)
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
        return {
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
        }
    finally:
        hvd.init()          # back onto the session's 8 CPU devices


@pytest.fixture(scope="module")
def readme_step():
    return _step(jax.devices()[:2])


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

def test_lowered_block_diffusion_step_carries_its_scopes_and_manifest():
    """The step of the second model family, lowered: its four scopes and
    the three kernel names in the text, and the routing manifest published
    under the program's name when the trace ends."""
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
        text = step.lower(params, opt.init(params), tokens).as_text(
            debug_info=True)
    finally:
        hvd.shutdown()
        hvd.init()          # back onto the session's 8 CPU devices
    for name in SDAR_SCOPES + KERNELS:
        assert name in text, name
    read = {name: _program_gauge(name, "bd_step")
            for name in tracing._ROUTING}
    assert read["moe_rows_bound"] == [2 * 64 * 2]
    assert 0 < read["bd_tiles_visited"][0] < read["bd_tiles_total"][0]


# ---------------------------------------------------------------------------
# the hybrid conv/attention decoder's names
# ---------------------------------------------------------------------------

def test_lowered_hybrid_step_carries_its_scopes_and_manifest():
    """The step of the third model family, lowered: its six scopes (two
    of them the expert layer's own) and the three kernel names in the text,
    and the routing manifest published under the program's name: the rows
    the routed layers are shaped for and the causal tiles of its one
    attention layer."""
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
        text = step.lower(params, opt.init(params), tokens).as_text(
            debug_info=True)
    finally:
        hvd.shutdown()
        hvd.init()          # back onto the session's 8 CPU devices
    for name in LFM2_SCOPES + KERNELS:
        assert name in text, name
    read = {name: _program_gauge(name, "hybrid_step")
            for name in tracing._ROUTING}
    assert read["moe_rows_bound"] == [2 * 32 * 2]
    assert 0 < read["causal_tiles_visited"][0] < read["causal_tiles_total"][0]
    assert read["bd_tiles_total"] == []


def test_lowered_latent_attention_step_carries_its_scopes_and_manifest():
    """The step of the fourth model family, lowered: its nine scopes (two
    of them the expert layer's own) and the three kernel names in the text,
    and the manifest published under the program's name: the rows the
    routed layers are shaped for, the causal tiles, what latent attention
    writes as expanded keys and values and the latent it expands, and that
    the multi-token-prediction module is in the step."""
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
        text = step.lower(params, opt.init(params), tokens).as_text(
            debug_info=True)
    finally:
        hvd.shutdown()
        hvd.init()          # back onto the session's 8 CPU devices
    for name in GLM4_SCOPES + KERNELS:
        assert name in text, name
    read = {name: _program_gauge(name, "latent_step")
            for name in tracing._ROUTING}
    assert read["moe_rows_bound"] == [2 * 32 * 2]
    assert 0 < read["causal_tiles_visited"][0] < read["causal_tiles_total"][0]
    assert read["bd_tiles_total"] == []
    # 64 positions x 4 attention layers (3 blocks and the module's) in bf16
    assert read["mla_kv_expanded_bytes"] == [64 * 4 * 4 * (16 + 16) * 2]
    assert read["mla_latent_bytes"] == [64 * 4 * (16 + 4) * 2]
    assert read["mtp_modules"] == [1]


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
    for key in ("unit", "layer", "moves", "source", "better", "workloads"):
        assert spec[key] == entry[0][key], key
    assert spec["workloads"] == ["sdar30b-bd-train-dp1", "lfm2-24b-train-dp1",
                                 "glm47f-train-dp1"]
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
                                              "glm47f-train-dp1"]
    for sel in spec["args"]["series"] + spec["args"]["per"]:
        assert tracing.NAMES[sel["name"]].feeds == spec["name"]
