"""A gradient sync that is handed what an earlier pass of the same trace
already averaged lowers nothing (``optimizer.allreduce_gradients``, the
marks of ``tracing.mark_synced``): when it is skipped, when it is not, and
that either way the step gives what two real passes gave.

"Today's" result is the same step with the marks turned off
(``tracing.mark_synced`` patched to do nothing): then every pass lowers, as
every pass did before a pass could be skipped."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu import overlap, tracing

N = 4
VG, OPT, GRAD = ("hvd/value_and_grad/sync", "hvd/optimizer/sync",
                 "hvd/grad/sync")
COLLECTIVES = ("stablehlo.all_reduce", "stablehlo.reduce_scatter",
               "stablehlo.all_gather", "stablehlo.all_to_all",
               "stablehlo.collective_permute")


@pytest.fixture(scope="module", autouse=True)
def four_devices():
    hvd.init(devices=jax.devices()[:N])
    yield
    hvd.init()          # back onto the session's 8 CPU devices


def _loss(p, x, y):
    h = jnp.tanh(x @ p["w1"] + p["b1"])
    return jnp.mean((h @ p["w2"] - y) ** 2)


def _state(d_in=16, d_hidden=32):
    k = jax.random.split(jax.random.PRNGKey(7), 5)
    params = {"w1": jax.random.normal(k[0], (d_in, d_hidden)) * 0.3,
              "b1": jax.random.normal(k[1], (d_hidden,)) * 0.1,
              "w2": jax.random.normal(k[2], (d_hidden, 4)) * 0.3}
    return (params, jax.random.normal(k[3], (4 * N, d_in)),
            jax.random.normal(k[4], (4 * N, 4)))


def _wide_state():
    """Gradients of 4.7 MB: one bucket over ``overlap.RS_AG_MIN_BYTES``,
    the size from which ``auto`` used to decompose on more than one
    device."""
    return _state(512, 2304)


_RUNS = iter(range(10 ** 6))


def _gauges(name, program):
    return {s["labels"]["scope"]: int(s["value"])
            for s in hvd.metrics.snapshot()["gauges"].get(name, ())
            if s["labels"].get("program") == program}


def _run(first=None, between=None, opt_kw=None, update_kw=None, own=False,
         state=_state):
    """One step on N devices: ``first(loss)(params, x, y)`` gives the
    gradients (default ``hvd.value_and_grad``), ``between`` touches them,
    ``hvd.DistributedOptimizer(adamw, **opt_kw).update`` takes them. Returns
    every device's new parameters and optimizer state, the lowered text's
    count of collectives and the manifest (none with ``own``: the step is
    under the caller's own ``shard_map`` and not ``hvd.spmd``)."""
    params, x, y = state()
    opt = hvd.DistributedOptimizer(optax.adamw(1e-2), **(opt_kw or {}))
    first = first or (lambda f: hvd.value_and_grad(f))

    def sync_once_step(params, opt_state, x, y):
        got = first(_loss)(params, x, y)
        grads = got[1] if isinstance(got, tuple) else got
        if between is not None:
            grads = between(grads)
        kw = update_kw() if update_kw else {}
        updates, opt_state = opt.update(grads, opt_state, params, **kw)
        new = optax.apply_updates(params, updates)
        return jax.tree_util.tree_map(lambda a: a[None], (new, opt_state))

    # a program name of its own, so that the manifest's gauges hold this
    # run's scopes and not the zeros of scopes an earlier run had
    name = sync_once_step.__name__ = f"sync_once_step_{next(_RUNS)}"
    specs = dict(in_specs=(P(), P(), P("hvd"), P("hvd")), out_specs=P("hvd"))
    if own:
        step = jax.jit(jax.shard_map(sync_once_step, mesh=hvd.mesh(),
                                     check_vma=False, **specs))
    else:
        step = hvd.spmd(sync_once_step, **specs)
    args = (params, opt.init(params), x, y)
    text = step.lower(*args).as_text()
    manifest = {} if own else {
        what: _gauges("grad_sync_" + what, name)
        for what in tracing._COUNTS}
    new, state = step(*args)
    return {"params": jax.tree_util.tree_map(np.asarray, new),
            "state": jax.tree_util.tree_map(np.asarray, state),
            "collectives": sum(text.count(c) for c in COLLECTIVES),
            **manifest}


def _today(monkeypatch, **kw):
    with monkeypatch.context() as m:
        m.setattr(tracing, "mark_synced", lambda tree, what: None)
        return _run(**kw)


def _assert_same(a, b):
    for key in ("params", "state"):
        la, lb = (jax.tree_util.tree_leaves(t[key]) for t in (a, b))
        assert len(la) == len(lb)
        for xa, xb in zip(la, lb):
            np.testing.assert_array_equal(xa, xb)
    assert jax.tree_util.tree_structure(a["state"]) \
        == jax.tree_util.tree_structure(b["state"])


def _replace_one(grads):
    return dict(grads, b1=grads["b1"] + 0)


def _alive():
    return {"alive": (hvd.rank() < N - 1).astype(jnp.float32)}


def _subset():
    return hvd.add_process_set([0, 1])


# every case: two passes lower, nothing is skipped, results are today's
NOT_SKIPPED = {
    "touched_in_between": dict(
        between=lambda g: jax.tree_util.tree_map(lambda a: a * 1, g)),
    "one_leaf_replaced": dict(between=_replace_one),
    "sum_then_average": dict(
        first=lambda f: hvd.value_and_grad(f, op=hvd.Sum)),
    "average_then_sum": dict(opt_kw=dict(op=hvd.Sum)),
    "adasum_on_the_optimizer": dict(opt_kw=dict(op=hvd.Adasum)),
    "another_process_set": dict(opt_kw=dict(process_set=_subset)),
    "alive_given": dict(update_kw=_alive),
    "prescale_factor": dict(opt_kw=dict(prescale_factor=2.0)),
    "postscale_factor": dict(opt_kw=dict(postscale_factor=0.5)),
    "error_feedback_on_a_quantized_wire": dict(
        opt_kw=dict(algorithm="rs_ag_int8", error_feedback=True)),
    "backward_passes_per_step": dict(
        opt_kw=dict(backward_passes_per_step=2)),
    "outside_hvd_spmd": dict(own=True),
}


@pytest.mark.parametrize("case", sorted(NOT_SKIPPED))
def test_this_second_pass_is_not_skipped(case, monkeypatch):
    kw = dict(NOT_SKIPPED[case])
    ps = None
    if callable((kw.get("opt_kw") or {}).get("process_set")):
        ps = kw["opt_kw"]["process_set"]()
        kw["opt_kw"] = dict(kw["opt_kw"], process_set=ps)
    try:
        got = _run(**kw)
        want = _today(monkeypatch, **kw)
    finally:
        if ps is not None:
            hvd.remove_process_set(ps)
    _assert_same(got, want)
    assert got["collectives"] == want["collectives"]
    if kw.get("own"):
        # no manifest outside hvd.spmd, so nothing is marked: twice the
        # README step's sync (the step drops the loss, so its all-reduce
        # is not in the text)
        once = _run()
        assert once["collectives"] >= 1
        assert got["collectives"] == 2 * once["collectives"]
        return
    # (optax.MultiSteps traces its inner update more than once)
    assert got["passes"] == {
        VG: 1, OPT: 1 if case != "backward_passes_per_step"
        else want["passes"][OPT]}
    assert got["skipped"] == {VG: 0, OPT: 0}
    assert got["bytes"][VG] > 0 and got["bytes"][OPT] > 0
    if case.startswith("error_feedback"):
        assert isinstance(opt_state := got["state"], hvd.ErrorFeedbackState)
        assert any(np.abs(r).max() > 0 for r in
                   jax.tree_util.tree_leaves(opt_state.residual))


def test_readme_step_gives_the_two_pass_steps_parameters(monkeypatch):
    """One AdamW step of the README path on 4 devices: one pass lowered,
    parameters and moments equal to the bit to the step that lowers both
    (the average of four equal values is that value)."""
    once, twice = _run(), _today(monkeypatch)
    assert once["passes"] == {VG: 1, OPT: 0}
    assert once["skipped"] == {VG: 0, OPT: 1}
    assert once["bytes"][OPT] == 0 and once["buckets"][OPT] == 0
    assert twice["passes"] == {VG: 1, OPT: 1}
    assert twice["bytes"] == {VG: once["bytes"][VG], OPT: once["bytes"][VG]}
    assert once["collectives"] >= 1
    assert twice["collectives"] == 2 * once["collectives"]
    _assert_same(once, twice)
    # every device holds the same new parameters
    for leaf in jax.tree_util.tree_leaves(once["params"]):
        assert leaf.shape[0] == N
        np.testing.assert_array_equal(leaf, np.broadcast_to(leaf[:1],
                                                            leaf.shape))


@pytest.mark.parametrize("wire", ["compression_fp16", "algorithm_rs_ag",
                                  "overlap", "fusion_threshold"])
def test_how_the_second_pass_would_travel_does_not_keep_it(wire):
    """compression, algorithm, overlap and the fusion threshold say how a
    pass travels, not what it returns: the pass is skipped all the same,
    and the step gives what the plain README step gives."""
    opt_kw = {"compression_fp16": dict(compression=hvd.Compression.fp16),
              "algorithm_rs_ag": dict(algorithm="rs_ag", overlap_chunks=2),
              "overlap": dict(overlap=True),
              "fusion_threshold": dict(fusion_threshold_bytes=256)}[wire]
    got, plain = _run(opt_kw=opt_kw), _run()
    assert got["passes"] == {VG: 1, OPT: 0}
    assert got["skipped"] == {VG: 0, OPT: 1}
    assert got["collectives"] == plain["collectives"]
    _assert_same(got, plain)


@pytest.mark.parametrize("first", ["value_and_grad", "grad", "tape",
                                   "allreduce_gradients"])
def test_every_plain_producer_marks_what_it_returns(first):
    producers = {
        "value_and_grad": (lambda f: hvd.value_and_grad(f), VG),
        "grad": (lambda f: hvd.grad(f), GRAD),
        "tape": (lambda f: lambda p, x, y: hvd.DistributedGradientTape()
                 .gradient(f, p, x, y), "hvd/tape/sync"),
        "allreduce_gradients": (
            lambda f: lambda p, x, y: hvd.allreduce_gradients(
                jax.grad(f)(p, x, y)), "none"),
    }
    fn, scope = producers[first]
    got = _run(first=fn)
    assert got["passes"] == {scope: 1, OPT: 0}
    assert got["skipped"] == {scope: 0, OPT: 1}


def test_overlap_taps_then_the_optimizer_lower_no_second_pass(monkeypatch):
    """hvd.grad(overlap=True) synchronises every group inside the backward
    (no pass of allreduce_gradients: the manifest has no entry for it) and
    marks what it returns, so the optimizer's pass is skipped."""
    kw = dict(first=lambda f: hvd.grad(f, overlap=True))
    got, today = _run(**kw), _today(monkeypatch, **kw)
    assert got["passes"] == {OPT: 0} and got["skipped"] == {OPT: 1}
    assert today["passes"] == {OPT: 1} and today["skipped"] == {OPT: 0}
    # the taps' collectives alone are left: what went is one plain pass
    # over the tree, which is all the README step has
    assert got["collectives"] >= 1
    assert today["collectives"] - got["collectives"] == _run()["collectives"]
    _assert_same(got, today)


def test_overlap_taps_with_sum_are_not_taken_for_an_average():
    got = _run(first=lambda f: hvd.grad(f, overlap=True, op=hvd.Sum))
    assert got["passes"] == {OPT: 1} and got["skipped"] == {OPT: 0}


def test_alive_result_is_not_marked():
    """The alive path divides by the live count, another quantity than
    the average over the process set: what it returns is not marked."""
    got = _run(first=lambda f: lambda p, x, y: hvd.allreduce_gradients(
        jax.grad(f)(p, x, y), alive=(hvd.rank() < N - 1).astype(jnp.float32)))
    assert got["passes"] == {"none": 1, OPT: 1}
    assert got["skipped"] == {"none": 0, OPT: 0}


def test_no_tracer_outlives_the_trace():
    """The marks hold the gradient tracers themselves; they go when
    ``tracing.program`` exits, an exception included."""
    with jax.checking_leaks():
        got = _run()
    assert got["skipped"] == {VG: 0, OPT: 1}
    assert getattr(tracing._TLS, "synced", None) is None
    assert getattr(tracing._TLS, "manifest", None) is None
    tracing.mark_synced({"a": jnp.ones(3)}, (hvd.Average, None))  # no-op
    assert tracing.synced_as({"a": jnp.ones(3)}) is None

    with pytest.raises(RuntimeError, match="boom"):
        with tracing.program("sync_once_step"):
            x = jnp.ones(3)
            tracing.mark_synced([x], "kept")
            assert tracing.synced_as([x]) == "kept"
            assert tracing.synced_as([x, x + 0]) is None    # one new leaf
            assert tracing.synced_as([]) is None
            raise RuntimeError("boom")
    assert getattr(tracing._TLS, "synced", None) is None


def test_marks_are_all_or_nothing_and_say_the_same_of_every_leaf():
    with tracing.program("sync_once_step"):
        a, b = jnp.ones(2), jnp.zeros(2)
        tracing.mark_synced({"a": a}, ("avg", 0))
        tracing.mark_synced({"b": b}, ("avg", 1))
        assert tracing.synced_as({"a": a}) == ("avg", 0)
        assert tracing.synced_as({"a": a, "b": b}) is None
        tracing.mark_synced({"b": b}, ("avg", 0))       # the later pass
        assert tracing.synced_as({"a": a, "b": b}) == ("avg", 0)
        with tracing.program("inner"):                  # its own marks
            assert tracing.synced_as({"a": a}) is None
        assert tracing.synced_as({"a": a}) == ("avg", 0)


# --- how the one pass travels by default (PR 32) -------------------------

def _lowered_as():
    return {s["labels"]["algorithm"]: int(s["value"])
            for s in hvd.metrics.snapshot()["counters"].get(
                "allreduce_algorithm_total", ())}


@pytest.fixture
def torus_2x2():
    """The four devices as the 2x2 a four-chip v5e host is."""
    os.environ["HOROVOD_TOPOLOGY"] = "2x2"
    try:
        hvd.init(devices=jax.devices()[:N])
        assert hvd.topology() == (2, 2)
        yield
    finally:
        del os.environ["HOROVOD_TOPOLOGY"]
        hvd.init(devices=jax.devices()[:N])


def test_readme_step_on_a_2x2_lowers_every_bucket_as_psum(torus_2x2):
    """The README AdamW step with nothing named, buckets over the old
    cutoff, on a detected 2x2: every bucket of the manifest is counted
    under ``psum`` and none under a ``_2d`` name, and the parameters are
    those of the step whose pass names ``algorithm="psum"``."""
    assert 4 * sum(a.size for a in jax.tree_util.tree_leaves(
        _wide_state()[0])) >= overlap.RS_AG_MIN_BYTES
    before = _lowered_as()
    got = _run(state=_wide_state)
    after = _lowered_as()
    assert got["passes"] == {VG: 1, OPT: 0} and got["buckets"][VG] >= 1
    # (one more for the loss: hvd.value_and_grad averages that scalar by
    # an allreduce of its own, which the manifest does not hold)
    assert after.get("psum", 0) - before.get("psum", 0) \
        == got["buckets"][VG] + 1
    moved = {name for name in after if after[name] != before.get(name, 0)}
    assert moved == {"psum"}, (before, after)
    named = _run(first=lambda f: jax.value_and_grad(f),
                 opt_kw=dict(algorithm="psum"), state=_wide_state)
    assert named["passes"] == {OPT: 1}
    _assert_same(got, named)
    # and an explicit name still lowers what it names
    before = _lowered_as()
    pinned = _run(first=lambda f: jax.value_and_grad(f),
                  opt_kw=dict(algorithm="rs_ag_2d"), state=_wide_state)
    assert _lowered_as().get("rs_ag_2d", 0) - before.get("rs_ag_2d", 0) \
        == pinned["buckets"][OPT]


def _parent_rule(requested, nbytes, op, world, reducible, wire=None,
                 topology=None, knob=None):
    """What ``auto`` resolved to before PR 32, beyond one device: by size
    and torus on every wire."""
    assert requested == "auto" and reducible
    if world <= 1:
        return "psum"
    two_d = "_2d" if overlap._torus_ndims(topology) >= 2 else ""
    if nbytes >= overlap.CHUNKED_MIN_BYTES:
        return overlap.compose_algorithm("chunked_rs_ag" + two_d, wire)
    if nbytes >= overlap.RS_AG_MIN_BYTES:
        return overlap.compose_algorithm("rs_ag" + two_d, wire)
    return "psum"


def test_one_device_step_does_not_depend_on_the_rule(monkeypatch):
    """On one device ``resolve_algorithm`` answers before any rule is
    looked at, so the step's jaxpr is the same text under this tree's rule
    and under the one it replaced: what the one-chip cells run cannot have
    changed. (On four devices the two rules part at this size.)"""
    params, x, y = _wide_state()
    opt = hvd.DistributedOptimizer(optax.adamw(1e-2))

    def one_device_step(params, opt_state, x, y):
        loss, grads = hvd.value_and_grad(_loss)(params, x, y)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    def jaxpr(rule):
        step = hvd.spmd(one_device_step,
                        in_specs=(P(), P(), P("hvd"), P("hvd")),
                        out_specs=(P(), P(), P()))
        before = _lowered_as()
        with monkeypatch.context() as m:
            if rule is not None:
                m.setattr(overlap, "resolve_algorithm", rule)
            text = str(jax.make_jaxpr(step)(params, opt.init(params), x, y))
        after = _lowered_as()
        return text, {k: after[k] - before.get(k, 0) for k in after
                      if after[k] != before.get(k, 0)}

    try:
        hvd.init(devices=jax.devices()[:1])
        ours, counted = jaxpr(None)
        theirs, counted_parent = jaxpr(_parent_rule)
        assert ours == theirs
        assert set(counted) == set(counted_parent) == {"psum"}
        hvd.init(devices=jax.devices()[:N])
        assert set(jaxpr(None)[1]) == {"psum"}
        assert set(jaxpr(_parent_rule)[1]) == {"psum", "rs_ag"}
    finally:
        hvd.init(devices=jax.devices()[:N])
