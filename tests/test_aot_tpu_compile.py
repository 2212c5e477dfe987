"""AOT compile guard: the programs chip_smoke.py runs, compiled for a
v5e 2x2 by the real TPU compiler (libtpu builds a topology description
with no chip attached), so a kernel or a step the chip's compiler refuses
fails here before any chip time is spent. The interpret choice of the
flash kernel is patched by the test; the product has no switch for it."""

import dataclasses
import importlib
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

import horovod_tpu as hvd
from horovod_tpu.models.gpt2 import GPT2, GPT2Config

pytest.importorskip("libtpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import chip_smoke  # noqa: E402

_FLASH = chip_smoke._sizes(False).flash


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies
    return topologies.get_topology_desc(
        topology_name="v5e:2x2", platform="tpu").devices


@pytest.fixture(scope="module", autouse=True)
def _keep_aot_programs_out_of_the_cache():
    # A topology-only client cannot load what it compiled, so an entry
    # written here would only ever be a failed read on the next run.
    name = "jax_persistent_cache_min_compile_time_secs"
    before = getattr(jax.config, name)
    jax.config.update(name, float("inf"))
    yield
    jax.config.update(name, before)


@pytest.fixture()
def mosaic(monkeypatch):
    """Compile the flash kernels for the chip instead of interpreting."""
    monkeypatch.setattr(
        importlib.import_module("horovod_tpu.ops.flash_attention"),
        "_use_interpret", lambda: False)


@pytest.fixture()
def restore_world():
    yield
    hvd.init()          # back onto the session's 8 CPU devices


def _shapes(tree, sharding):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _kernel_calls(hlo, kernel):
    return re.findall(rf"%{kernel}[.\d]* = [^\n]*custom-call\(", hlo)


def _assert_kernels_named(hlo, bwd_kernels=2):
    """The Mosaic custom calls carry the names the per-kernel metrics sum
    by (``pallas_call(name=)``, ``tracing.NAMES``): a refactor that loses
    one would leave ``flash_*_ms.train`` without a reading. ``flash_dq`` is
    there exactly where the backward is two kernels: with a resident K
    tile and a loop of chunks ``flash_dkv`` yields dQ too."""
    for kernel in ("flash_fwd", "flash_dkv"):
        assert _kernel_calls(hlo, kernel), kernel
    assert bool(_kernel_calls(hlo, "flash_dq")) == (bwd_kernels == 2)


# The chip smoke's variants whose table entry keeps K resident with a loop
# of chunks (head 64 at T 1024, whatever the dtype: the nearest entry): no
# key bias among them, so their backward is the one kernel.
_ONE_KERNEL = {"causal d64 T1024 bf16", "packed d64 T1024 bf16",
               "strict causal (offset -1) d64 T1024 bf16",
               "causal d64 T1024 fp32"}


@pytest.mark.parametrize("variant", _FLASH, ids=[v[0] for v in _FLASH])
def test_flash_variant_compiles_for_v5e(v5e, mosaic, variant):
    _, B, T, H, D, dtype, causal, packed, masked, offset = variant
    on = SingleDeviceSharding(v5e[0])
    x = jax.ShapeDtypeStruct((B, T, H, D), jnp.dtype(dtype), sharding=on)
    seg = jax.ShapeDtypeStruct((B, T), jnp.int32, sharding=on) \
        if packed else None
    mask = jax.ShapeDtypeStruct((B, T), jnp.bool_, sharding=on) \
        if masked else None

    def fwd_bwd(q, k, v, seg, mask):
        def loss(q, k, v):
            o = chip_smoke._flash_attention(
                q, k, v, causal=causal, key_mask=mask, seg=seg,
                offset=offset)
            return jnp.sum(o.astype(jnp.float32))
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    lowered = jax.jit(fwd_bwd).lower(x, x, x, seg, mask)
    assert "tpu_custom_call" in lowered.as_text()
    _assert_kernels_named(lowered.compile().as_text(),
                          1 if variant[0] in _ONE_KERNEL else 2)


_COLLECTIVE = re.compile(r" (all-reduce|all-gather|reduce-scatter|all-to-all|"
                         r"collective-permute)(-start)?\(")


@pytest.mark.parametrize("n_dev", [1, 4])
def test_spmd_train_step_compiles_for_v5e(v5e, mosaic, restore_world, n_dev):
    hvd.init(devices=v5e[:n_dev])
    assert hvd.topology() == ((2, 2) if n_dev == 4 else (1,))
    cfg = dataclasses.replace(GPT2Config.medium(), num_layers=2,
                              attention="flash", remat=True,
                              remat_policy="dots")
    opt, step = chip_smoke._train_step(hvd, cfg)
    replicated = NamedSharding(hvd.mesh(), P())
    params = jax.eval_shape(lambda: chip_smoke._init_params(cfg))
    tokens = jax.ShapeDtypeStruct((8 * n_dev, 1024), jnp.int32,
                                  sharding=hvd.spmd_data_sharding())
    lowered = step.lower(_shapes(params, replicated),
                         _shapes(jax.eval_shape(opt.init, params),
                                 replicated), tokens)
    assert "tpu_custom_call" in lowered.as_text()
    hlo = lowered.compile().as_text()
    # the gradient sync is in the program exactly when there is a peer
    assert (" all-reduce(" in hlo) == (n_dev > 1)
    if n_dev == 1:
        assert not _COLLECTIVE.search(hlo)
    else:
        # `auto` hands every bucket to the fabric's own all-reduce over
        # the four: no pairwise phases of a `_2d` schedule, and none of
        # the gathers and slices a decomposition brings with it
        reduces = [line for line in hlo.splitlines()
                   if re.search(r" all-reduce(-start)?\(", line)]
        assert reduces
        for line in reduces:
            assert "replica_groups={{0,1,2,3}}" in line, line[:300]
        sync = [line for line in hlo.splitlines()
                if "hvd/value_and_grad/sync" in line]
        assert sync
        for op in (" all-gather(", " all-gather-start(", " reduce-scatter(",
                   " dynamic-slice("):
            assert not [line[:200] for line in sync if op in line], op
    # the program keeps its name, the kernels theirs, and the trainer's
    # scopes reach the chip's program as operation metadata
    assert hlo.startswith("HloModule jit_train_step")
    _assert_kernels_named(hlo, bwd_kernels=1)
    # remat=dots keeps what the forward kernel wrote: one forward call a
    # layer in the chip's program, not a second one in the backward; and
    # the backward is one kernel a layer (head 64 at T 1024: K resident)
    for kernel, a_layer in (("flash_fwd", 1), ("flash_dq", 0),
                            ("flash_dkv", 1)):
        calls = _kernel_calls(hlo, kernel)
        assert len(calls) == a_layer * cfg.num_layers, (kernel, calls)
    for scope in ("hvd/value_and_grad/sync", "hvd/optimizer/update",
                  "hvd/fusion/pack", "hvd/fusion/unpack", "gpt2/loss_head"):
        assert scope in hlo, scope
    # the optimizer is handed what hvd.value_and_grad averaged: its own
    # pass is skipped, and no operation is left to carry its scope
    assert "hvd/optimizer/sync" not in hlo


def test_block_diffusion_flash_compiles_for_v5e_at_the_cells_shape(v5e,
                                                                   mosaic):
    """Head size 128, 8,192 positions ``[noisy ; clean]`` on a plain grid:
    the mask's integer arithmetic and the tile skip lower for the chip in
    all three kernels (a select between booleans did not)."""
    on = SingleDeviceSharding(v5e[0])
    x = jax.ShapeDtypeStruct((1, 8192, 8, 128), jnp.bfloat16, sharding=on)
    # PR 27's row of the table, named: the plain grid, which a shape the
    # chunk loop cannot take still runs
    lowered = _bd_fwd_bwd(block_q=1024, block_k=1024).lower(x, x, x)
    assert _vmem_asked(lowered) == []
    _assert_kernels_named(lowered.compile().as_text())


def _bd_fwd_bwd(**tiles):
    from horovod_tpu.ops.flash_attention import flash_attention

    def fwd_bwd(q, k, v):
        return jax.grad(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, block_diffusion=(4096, 4), **tiles)
            .astype(jnp.float32)), argnums=(0, 1, 2))(q, k, v)
    return jax.jit(fwd_bwd)


def test_block_diffusion_flash_is_resident_and_one_kernel_at_the_cells_shape(
        v5e, mosaic):
    """The same shape by the table's row since PR 38, at the cell's 32
    heads a row: K resident forward and backward with a loop over what the
    mask shows (``_bd_chunks``), the backward one kernel (no ``flash_dq``
    in the text, the routing manifest's ``flash_bwd_kernels`` 1), compiled
    with the VMEM the code itself asks for, which a core has."""
    from horovod_tpu import tracing
    from horovod_tpu.ops import tile_table
    fa = importlib.import_module("horovod_tpu.ops.flash_attention")
    entry = tile_table._best_entry(128, 8192, "bfloat16", "block_diffusion",
                                   None)
    assert (entry["head_dim"], entry["seq"]) == (128, 8192)
    assert entry["block_k"] == entry["block_k_bwd"] == 8192
    assert entry["chunk"] < 8192 and entry["chunk_bwd"] < 8192
    on = SingleDeviceSharding(v5e[0])
    x = jax.ShapeDtypeStruct((2, 8192, 32, 128), jnp.bfloat16, sharding=on)
    with tracing.program("bd_aot"):
        lowered = _bd_fwd_bwd().lower(x, x, x)
    gauges = hvd.metrics.snapshot()["gauges"]
    read = {name: [s["value"] for s in gauges.get(name, ())
                   if s["labels"].get("program") == "bd_aot"]
            for name in ("flash_bwd_kernels", "flash_bwd_vmem_bytes",
                         "bd_tiles_visited", "bd_tiles_total")}
    assert read["flash_bwd_kernels"] == [1]
    asked = _vmem_asked(lowered)
    assert asked and read["flash_bwd_vmem_bytes"] == [asked[-1]]
    assert all(fa._VMEM_DEFAULT < n <= fa._VMEM_CAP for n in asked)
    assert (read["bd_tiles_visited"][0], read["bd_tiles_total"][0]) == (
        fa.bd_tiles(4096, 4, entry["block_q"], 8192, entry["chunk"], d=128,
                    itemsize=2))
    assert read["bd_tiles_visited"][0] / read["bd_tiles_total"][0] <= 0.375
    hlo = lowered.compile().as_text()
    _assert_kernels_named(hlo, bwd_kernels=1)
    assert "flash_dq" not in hlo


def test_block_diffusion_step_compiles_for_v5e_at_published_widths(
        v5e, mosaic, restore_world):
    """One layer of the second family's step at its published widths and
    the cell's 2 x 4,096 clean tokens: the flash kernels under the mask,
    XLA's grouped kernel for the experts held, the four scopes."""
    import optax
    from horovod_tpu.models import sdar
    hvd.init(devices=v5e[:1])
    cfg = sdar.SDARConfig(vocab_size=18992, num_layers=1,
                          experts_held=(0, 16), attention="flash",
                          remat=True)
    model = sdar.SDAR(cfg)
    opt = hvd.DistributedOptimizer(optax.adamw(3e-4))

    def train_step(params, opt_state, tokens):
        noise = sdar.block_noise(
            jax.random.split(jax.random.PRNGKey(0), tokens.shape[0]),
            tokens.shape[1], cfg.block_len)
        loss, grads = hvd.value_and_grad(
            lambda p: sdar.loss_fn(model, p, tokens, noise))(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    step = hvd.spmd(train_step, in_specs=(P(), P(), P("hvd")),
                    out_specs=(P(), P(), P()), donate_argnums=(0, 1))
    replicated = NamedSharding(hvd.mesh(), P())
    twin = sdar.SDAR(dataclasses.replace(cfg, attention="dense",
                                         remat=False))
    row = jnp.zeros((1, 8), jnp.int32)
    params = jax.eval_shape(
        lambda: twin.init(jax.random.PRNGKey(0), row, row)["params"])
    tokens = jax.ShapeDtypeStruct((2, 4096), jnp.int32,
                                  sharding=hvd.spmd_data_sharding())
    hlo = step.lower(_shapes(params, replicated),
                     _shapes(jax.eval_shape(opt.init, params), replicated),
                     tokens).compile().as_text()
    # since PR 38 the table's row keeps K resident under the mask too: the
    # layer's backward is one kernel
    _assert_kernels_named(hlo, bwd_kernels=1)
    assert re.search(r"%ragged-dot[^\n]* = [^\n]*custom-call\(", hlo)
    for scope in ("sdar/attn", "moe/route", "moe/experts",
                  "sdar/loss_head"):
        assert scope in hlo, scope


def _causal_fwd_bwd(head, scale=None):
    from horovod_tpu.ops.flash_attention import flash_attention

    def fwd_bwd(q, k, v):
        return jax.grad(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=True, scale=scale).astype(jnp.float32)),
            argnums=(0, 1, 2))(q, k, v)
    return jax.jit(fwd_bwd)


def _vmem_asked(lowered):
    """Bytes of scoped VMEM the Mosaic calls of a lowered text ask for
    (``vmem_limit_bytes``), one a call that asks: a call under the
    compiler's default carries no ``scoped_memory_configs``."""
    return [int(n) for n in re.findall(
        r"scoped_memory_configs.{0,80}?size[^:]*: *(\d+)", lowered.as_text())]


def test_causal_flash_compiles_for_v5e_at_the_hybrid_cells_shape(v5e, mosaic):
    """Head size 64, 8,192 positions, 32 query heads a row, the tiles of
    the table's row for that shape: the causal kernels' second shape in
    the benchmark. K is resident forward and backward, the backward is one
    kernel, and it compiles with the VMEM the code itself asks for."""
    from horovod_tpu.ops import tile_table
    entry = tile_table._best_entry(64, 8192, "bfloat16", "causal", None)
    assert (entry["head_dim"], entry["seq"]) == (64, 8192)
    on = SingleDeviceSharding(v5e[0])
    x = jax.ShapeDtypeStruct((1, 8192, 32, 64), jnp.bfloat16, sharding=on)
    lowered = _causal_fwd_bwd(64).lower(x, x, x)
    # the forward fits the default; the backward's resident tile does not
    assert _vmem_asked(lowered) == [42598400]
    _assert_kernels_named(lowered.compile().as_text(), bwd_kernels=1)


def test_the_compilers_default_refuses_a_resident_backward_at_8k(
        v5e, mosaic, monkeypatch):
    """What the ask is for: the same kernels handed to the compiler with
    no ``vmem_limit_bytes`` run out of its default 16 MiB of scoped VMEM
    (this was the wall PRs 31 and 33 met, not the chip's 128 MiB)."""
    fa = importlib.import_module("horovod_tpu.ops.flash_attention")
    monkeypatch.setattr(fa, "_vmem_params", lambda chunk, need: {})
    on = SingleDeviceSharding(v5e[0])
    x = jax.ShapeDtypeStruct((1, 8192, 20, 256), jnp.bfloat16, sharding=on)
    lowered = _causal_fwd_bwd(256, 256 ** -0.5).lower(x, x, x)
    assert _vmem_asked(lowered) == []
    with pytest.raises(Exception, match="(?i)vmem"):
        lowered.compile()


@pytest.fixture(scope="module")
def hybrid_hlo(v5e):
    """The compiled text of the third family's step at its published widths
    and the cell's 8,192-token rows, one row and one layer of each kind
    (conv + dense, attention + routed, conv + routed)."""
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(
            importlib.import_module("horovod_tpu.ops.flash_attention"),
            "_use_interpret", lambda: False)
        try:
            return _hybrid_hlo(v5e)
        finally:
            hvd.init()      # back onto the session's 8 CPU devices


def _hybrid_hlo(v5e):
    import optax
    from horovod_tpu.models import lfm2
    hvd.init(devices=v5e[:1])
    cfg = lfm2.LFM2Config(vocab_size=8192, num_layers=3,
                          layer_types=("conv", "full_attention", "conv"),
                          num_dense_layers=1, experts_held=(0, 8),
                          attention="flash", remat=True)
    model = lfm2.LFM2(cfg)
    bias = np.zeros((cfg.num_layers, cfg.experts_total), np.float32)
    opt = hvd.DistributedOptimizer(optax.adamw(1e-5))

    def train_step(params, opt_state, tokens):
        loss, grads = hvd.value_and_grad(
            lambda p: lfm2.loss_fn(model, p, tokens, bias))(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    step = hvd.spmd(train_step, in_specs=(P(), P(), P("hvd")),
                    out_specs=(P(), P(), P()), donate_argnums=(0, 1))
    replicated = NamedSharding(hvd.mesh(), P())
    twin = lfm2.LFM2(dataclasses.replace(cfg, attention="dense",
                                         remat=False))
    params = jax.eval_shape(lambda: twin.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    tokens = jax.ShapeDtypeStruct((1, 8192), jnp.int32,
                                  sharding=hvd.spmd_data_sharding())
    return step.lower(_shapes(params, replicated),
                      _shapes(jax.eval_shape(opt.init, params), replicated),
                      tokens).compile().as_text()


def test_hybrid_step_compiles_for_v5e_at_published_widths(hybrid_hlo):
    """The causal flash kernels, XLA's grouped kernel for the experts held,
    the six scopes."""
    hlo = hybrid_hlo
    _assert_kernels_named(hlo, bwd_kernels=1)
    assert re.search(r"%ragged-dot[^\n]* = [^\n]*custom-call\(", hlo)
    for scope in ("lfm2/shortconv", "lfm2/attn", "lfm2/dense_mlp",
                  "moe/route", "moe/experts", "lfm2/loss_head"):
        assert scope in hlo, scope


def test_scope_table_of_the_chips_hybrid_step(hybrid_hlo):
    """``tracing.scope_table``'s pass over what the chip's compiler wrote:
    no kernel is unscoped (the flash kernels lie in their wrapper's scope,
    XLA's grouped kernel carries no metadata and has the expert layer
    through its ``ragged-dot`` row); the loops over windows are containers
    whose bodies' instructions, the grouped kernel among them, are rows of
    their own; the copies between [B,T,H,D] and the kernels' layout are
    rows of ``flash/layout``, in the forward and in its re-run (at one row a
    step the backward's are bitcasts); and every instruction the entry
    computation runs is in the table."""
    from horovod_tpu import tracing
    table = tracing._read_scopes(hybrid_hlo)
    kernels = {n: r for n, r in table.items() if r.kernel}
    # the backward of the causal kernels is the one that also yields dQ
    assert {r.kernel for r in kernels.values()} == {
        "flash_fwd", "flash_dkv", "ragged-dot"}
    for name, row in kernels.items():
        assert row.layer is not None and not row.container, name
        if row.kernel == "ragged-dot":
            assert row.layer == tracing.NAMES["moe/experts"].layer
        else:
            assert row.scopes[-2:] == ("lfm2/attn", "flash_attention"), name
    loops = {n: r for n, r in table.items() if r.container}
    assert any(n.startswith("while") and "moe/experts" in r.scopes
               for n, r in loops.items())
    assert any("/while/body/" in r.op_name and "moe/experts" in r.scopes
               and not r.container for r in table.values())
    layout = [r for r in table.values() if "flash/layout" in r.scopes]
    assert {"fwd", "remat"} <= {r.direction for r in layout}
    assert all(r.scopes[-2:] == ("lfm2/attn", "flash/layout") for r in layout)
    for scope in ("lfm2/shortconv", "lfm2/dense_mlp", "moe/route",
                  "lfm2/loss_head", "hvd/optimizer/update"):
        assert any(scope in r.scopes for r in table.values()), scope
    assert {"fwd", "remat", "bwd"} == {r.direction for r in table.values()}
    entry = hybrid_hlo[hybrid_hlo.index("\nENTRY "):]
    entry = entry[:entry.index("\n}")]
    assert set(re.findall(r"^\s+(?:ROOT )?%?([\w.\-]+) = ", entry, re.M)) \
        <= set(table)


def test_latent_attention_kernels_compile_for_v5e_at_the_table_entry(
        v5e, mosaic):
    """The causal kernels at the table's own entry for head 256 and T
    8,192 (the latent-attention cell's shape: 20 heads, each with its own
    expanded key and value), forward and backward: K resident in both,
    one backward kernel, each with the VMEM the code asks for."""
    from horovod_tpu.ops import tile_table
    entry = tile_table._best_entry(256, 8192, "bfloat16", "causal", None)
    assert (entry["head_dim"], entry["seq"]) == (256, 8192)
    on = SingleDeviceSharding(v5e[0])
    x = jax.ShapeDtypeStruct((1, 8192, 20, 256), jnp.bfloat16, sharding=on)
    lowered = _causal_fwd_bwd(256, 256 ** -0.5).lower(x, x, x)
    assert sorted(_vmem_asked(lowered)) == [25559040, 72744960]
    _assert_kernels_named(lowered.compile().as_text(), bwd_kernels=1)


def test_latent_attention_step_compiles_for_v5e_at_published_widths(
        v5e, mosaic, restore_world):
    """The fourth family's step at its published widths and the cell's
    8,192-token rows, one row, a block of each kind (dense, routed + shared)
    and the multi-token-prediction module: the causal flash kernels at head
    256, XLA's grouped kernel for the experts held, the nine scopes."""
    import optax
    from horovod_tpu.models import glm4_moe_lite as glm
    hvd.init(devices=v5e[:1])
    cfg = glm.Glm4MoeLiteConfig(vocab_size=19360, num_layers=2,
                                experts_held=(0, 8), attention="flash",
                                remat=True)
    model = glm.Glm4MoeLite(cfg)
    bias = np.zeros((cfg.num_layers + 1, cfg.experts_total), np.float32)
    opt = hvd.DistributedOptimizer(optax.adamw(1e-5))

    def train_step(params, opt_state, tokens):
        loss, grads = hvd.value_and_grad(
            lambda p: glm.loss_fn(model, p, tokens, bias))(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    step = hvd.spmd(train_step, in_specs=(P(), P(), P("hvd")),
                    out_specs=(P(), P(), P()), donate_argnums=(0, 1))
    replicated = NamedSharding(hvd.mesh(), P())
    twin = glm.Glm4MoeLite(dataclasses.replace(cfg, attention="dense",
                                               remat=False))
    params = jax.eval_shape(lambda: twin.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    tokens = jax.ShapeDtypeStruct((1, 8192), jnp.int32,
                                  sharding=hvd.spmd_data_sharding())
    hlo = step.lower(_shapes(params, replicated),
                     _shapes(jax.eval_shape(opt.init, params), replicated),
                     tokens).compile().as_text()
    _assert_kernels_named(hlo, bwd_kernels=1)
    assert re.search(r"%ragged-dot[^\n]* = [^\n]*custom-call\(", hlo)
    for scope in ("glm4/mla_down", "glm4/mla_up", "glm4/attn",
                  "glm4/dense_mlp", "glm4/shared_expert", "glm4/mtp",
                  "glm4/loss_head", "moe/route", "moe/experts"):
        assert scope in hlo, scope


@pytest.mark.parametrize("window", [None, 4096], ids=["causal", "window"])
def test_flash_compiles_for_v5e_at_the_16k_table_entries(v5e, mosaic, window):
    """Head size 128, 16,384 positions, 28 query heads a row: the causal
    kernels' fourth shape in the benchmark, without a window (the global
    layer) and under one of 4,096 keys, at the table's own entries for the
    two kinds. K is resident forward and backward, the backward is one
    kernel whether or not the loop starts at the band's lower edge, and each
    call compiles with the VMEM the code itself asks for."""
    from horovod_tpu.ops import tile_table
    from horovod_tpu.ops.flash_attention import flash_attention
    fa = importlib.import_module("horovod_tpu.ops.flash_attention")
    kind = "window" if window else "causal"
    entry = tile_table._best_entry(128, 16384, "bfloat16", kind, None)
    assert (entry["head_dim"], entry["seq"], entry["kind"]) == (
        128, 16384, kind)
    bq, bk, bqb, bkb, chunk, chunk_bwd = tile_table.lookup_full(
        128, 16384, "bfloat16", kind)
    on = SingleDeviceSharding(v5e[0])
    x = jax.ShapeDtypeStruct((1, 16384, 28, 128), jnp.bfloat16, sharding=on)
    lowered = jax.jit(lambda q, k, v: jax.grad(
        lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=True, window=window).astype(jnp.float32)),
        argnums=(0, 1, 2))(q, k, v)).lower(x, x, x)
    assert sorted(_vmem_asked(lowered)) == sorted([
        fa._vmem_need("fwd", bq, bk, chunk, 128, 2),
        fa._vmem_need("dkv", bqb, bkb, chunk_bwd, 128, 2, extra="dq")])
    _assert_kernels_named(lowered.compile().as_text(), bwd_kernels=1)


def test_latent_attention_without_positions_compiles_at_its_16k_entry(
        v5e, mosaic):
    """Keys of 192 beside values of 128, 16,384 positions, 32 heads: the
    latent-attention layer of the KDA cell at the table's own entry for
    head 192, forward and backward, the value tiles 128 wide; each call
    compiles with the VMEM the code asks for at that value width, and the
    backward is one kernel where the entry's tiles let the K tile stay
    resident."""
    from horovod_tpu.ops import tile_table
    from horovod_tpu.ops.flash_attention import flash_attention
    fa = importlib.import_module("horovod_tpu.ops.flash_attention")
    entry = tile_table._best_entry(192, 16384, "bfloat16", "causal", None)
    assert (entry["head_dim"], entry["seq"]) == (192, 16384)
    bq, bk, bqb, bkb, chunk, chunk_bwd = tile_table.lookup_full(
        192, 16384, "bfloat16", "causal")
    on = SingleDeviceSharding(v5e[0])
    qk = jax.ShapeDtypeStruct((1, 16384, 32, 192), jnp.bfloat16, sharding=on)
    v = jax.ShapeDtypeStruct((1, 16384, 32, 128), jnp.bfloat16, sharding=on)
    lowered = jax.jit(lambda q, k, v: jax.grad(
        lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=True).astype(jnp.float32)),
        argnums=(0, 1, 2))(q, k, v)).lower(qk, qk, v)
    fwd = fa._tiling(16384, 16384, bq, bk, chunk, True, None, d=192,
                     dv=128)
    dkv = fa._tiling(16384, 16384, bqb, bkb, chunk_bwd, True, None, d=192,
                     dv=128, kernel="dkv")
    asked = [fa._vmem_need("fwd", *fwd, 192, 2, dv=128)] if fwd[2] else []
    if dkv[2]:
        asked.append(fa._vmem_need("dkv", *dkv, 192, 2, extra="dq",
                                   dv=128))
    assert sorted(_vmem_asked(lowered)) == sorted(asked)
    _assert_kernels_named(lowered.compile().as_text(),
                          bwd_kernels=1 if dkv[2] else 2)


def test_kda_compiles_for_v5e_at_the_cells_shape(v5e, mosaic):
    """The chunked gated delta rule and its own backward at the KDA cell's
    shape (one row of 16,384, 32 heads of 128; q, k, v in bfloat16, decays
    and write strengths in float32) compile for a described v5e: the
    forward one Mosaic kernel, ``kda_fwd``, and the backward one,
    ``kda_bwd``, each called once, each asking for the VMEM it counts
    where that is over the compiler's default and under the flash
    kernels' cap; and nothing of a size that grows with positions x
    positions."""
    kda = importlib.import_module("horovod_tpu.ops.kda")
    fa = importlib.import_module("horovod_tpu.ops.flash_attention")
    on = SingleDeviceSharding(v5e[0])
    x = jax.ShapeDtypeStruct((1, 16384, 32, 128), jnp.bfloat16, sharding=on)
    g = jax.ShapeDtypeStruct((1, 16384, 32, 128), jnp.float32, sharding=on)
    b = jax.ShapeDtypeStruct((1, 16384, 32), jnp.float32, sharding=on)
    lowered = jax.jit(jax.grad(
        lambda q, k, v, g, b: jnp.sum(kda.chunk_kda(q, k, v, g, b).astype(
            jnp.float32)), argnums=(0, 1, 2, 3, 4))).lower(x, x, x, g, b)
    need = [kda._vmem_need(kda.HEADS, kda.CHUNK, 128, 128, 2),
            kda._bwd_vmem_need(kda.HEADS, kda.CHUNK, 128, 128, kda.GROUP,
                               2)]
    assert all(n < fa._VMEM_CAP for n in need)
    assert sorted(_vmem_asked(lowered)) == sorted(
        n for n in need if n > fa._VMEM_DEFAULT)
    compiled = lowered.compile()
    hlo = compiled.as_text()
    # named kda_fwd, jvp_kda_fwd_ where no scope of a caller is round it
    # (transpose_jvp_kda_bwd__ the backward)
    for kernel in ("kda_fwd", "kda_bwd"):
        assert len(re.findall(rf"%\w*{kernel}\w*[.\d]* = [^\n]*custom-call\(",
                              hlo)) == 1, kernel
    assert hlo.count('custom_call_target="tpu_custom_call"') == 2
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 4e9, mem.temp_size_in_bytes


def test_a_narrow_leaf_is_unpacked_without_reshaping_its_bucket(v5e):
    """A (4096, 4) leaf (a short convolution's kernel) fused behind a
    (1024, 1024) one: the chip's compiler, left to itself, reshapes the
    whole bucket to (N, 4) before slicing the leaf out, each row of 4
    padded to 128 lanes, 32 times the bucket in temporaries. The unpack
    slices the leaf out first, so the step needs none."""
    from horovod_tpu import fusion
    on = SingleDeviceSharding(v5e[0])
    leaves = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=on)
              for s in ((1024, 1024), (4096, 4))]
    compiled = jax.jit(lambda ls: fusion.fused_apply(
        lambda b: b * 2.0, ls)).lower(leaves).compile()
    assert compiled.memory_analysis().temp_size_in_bytes == 0


def test_window_attention_step_compiles_for_v5e_and_fits_the_chip(
        v5e, mosaic, restore_world):
    """The fifth family's step as the cell runs it: the published widths,
    one whole period (a global layer without positions, three window layers
    with RoPE), 8 of 64 experts, the vocabulary slice, 2 rows of 16,384
    tokens, ``remat=dots``, AdamW: the flash kernels with one backward
    kernel, XLA's grouped kernel, the family's scopes, and the compiler's
    count of the step's memory under the chip's 15.75 GiB."""
    import optax
    from horovod_tpu.models import smallthinker as st
    hvd.init(devices=v5e[:1])
    cfg = st.SmallThinkerConfig(
        vocab_size=18992, num_layers=4, sliding_window_layout=(0, 1, 1, 1),
        rope_layout=(0, 1, 1, 1), experts_held=(0, 8), attention="flash",
        remat=True, remat_policy="dots")
    model = st.SmallThinker(cfg)
    opt = hvd.DistributedOptimizer(optax.adamw(1e-5))

    def train_step(params, opt_state, tokens):
        loss, grads = hvd.value_and_grad(
            lambda p: st.loss_fn(model, p, tokens))(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        return (optax.apply_updates(params, updates), opt_state, loss,
                optax.global_norm(grads))

    step = hvd.spmd(train_step, in_specs=(P(), P(), P("hvd")),
                    out_specs=(P(), P(), P(), P()), donate_argnums=(0, 1))
    replicated = NamedSharding(hvd.mesh(), P())
    twin = st.SmallThinker(dataclasses.replace(cfg, attention="dense",
                                               remat=False))
    params = jax.eval_shape(lambda: twin.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    assert sum(x.size for x in jax.tree_util.tree_leaves(params)) \
        == 370_547_200
    tokens = jax.ShapeDtypeStruct((2, 16384), jnp.int32,
                                  sharding=hvd.spmd_data_sharding())
    compiled = step.lower(
        _shapes(params, replicated),
        _shapes(jax.eval_shape(opt.init, params), replicated),
        tokens).compile()
    mem = compiled.memory_analysis()
    counted = (mem.argument_size_in_bytes + mem.output_size_in_bytes
               - mem.alias_size_in_bytes + mem.temp_size_in_bytes
               + mem.generated_code_size_in_bytes)
    assert 10e9 < counted < 15.75 * 2 ** 30, counted
    hlo = compiled.as_text()
    _assert_kernels_named(hlo, bwd_kernels=1)
    assert len(_kernel_calls(hlo, "flash_fwd")) == 4     # dots keeps them
    assert re.search(r"%ragged-dot[^\n]* = [^\n]*custom-call\(", hlo)
    for scope in ("smallthinker/block", "smallthinker/attn_global",
                  "smallthinker/attn_window", "smallthinker/loss_head",
                  "moe/route", "moe/experts", "flash/layout"):
        assert scope in hlo, scope


def test_engine_programs_compile_for_v5e_with_cache_donation(
        v5e, restore_world, monkeypatch):
    from horovod_tpu.serving import InferenceEngine
    hvd.init(devices=v5e[:1])
    cfg = dataclasses.replace(GPT2Config.medium(), num_layers=2)
    params = jax.tree_util.tree_map(
        lambda x: np.zeros(x.shape, jnp.bfloat16),
        jax.eval_shape(lambda: chip_smoke._init_params(cfg)))
    # the engine donates its cache only off-CPU; take that branch
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    eng = InferenceEngine(GPT2(cfg), params, slots=8, max_len=1024,
                          block_size=16, prefix_cache=True, name="aot")
    monkeypatch.undo()
    assert eng._donate == (1,)
    on = SingleDeviceSharding(v5e[0])

    def vec(*shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=on)

    tail = (vec(8), vec(8), vec(8, dtype=jnp.bool_), vec(8), vec(8), None)
    state = (_shapes(eng.params, on), _shapes(eng._cache, on))
    for pure, steps in ((eng._decode_pure, eng.spec_k + 1),
                        (eng._prefill_pure, eng.prefill_chunk)):
        mem = jax.jit(pure, donate_argnums=eng._donate).lower(
            *state, vec(steps, 8), *tail).compile().memory_analysis()
        # the K/V pools are updated in place, not copied every token
        assert mem.alias_size_in_bytes >= eng._pool_bytes
