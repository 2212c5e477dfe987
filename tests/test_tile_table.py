"""Tile-table lookup wiring (VERDICT r3 item 2).

Upstream analogue: horovod/runner/autotune ships tuned fusion parameters;
here the tuned artifact is the checked-in flash-tile table that
``flash_attention``/``ring_flash_attention``/``ulysses_attention`` consult
by default. CPU tests pin the lookup wiring; on-chip numbers regenerate the
data via ``tools/tune_tiles.py``.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import tile_table


@pytest.fixture
def tmp_table(tmp_path):
    p = tmp_path / "tiles.json"
    tile_table.save_table({
        "version": 1, "device": "test",
        "default": {"block_q": 64, "block_k": 128},
        "entries": [
            {"head_dim": 64, "seq": 1024, "dtype": "bfloat16",
             "kind": "causal", "block_q": 256, "block_k": 512,
             "us_per_call": 10.0, "source": "test"},
            {"head_dim": 64, "seq": 8192, "dtype": "bfloat16",
             "kind": "causal", "block_q": 512, "block_k": 1024,
             "us_per_call": 20.0, "source": "test"},
            {"head_dim": 128, "seq": 1024, "dtype": "float32",
             "kind": "full", "block_q": 128, "block_k": 256,
             "us_per_call": 30.0, "source": "test"},
            {"head_dim": 64, "seq": 1024, "dtype": "bfloat16",
             "kind": "ring", "block_q": 128, "block_k": 512,
             "us_per_call": 40.0, "source": "test"},
        ]}, p)
    return p


def test_exact_match(tmp_table):
    assert tile_table.lookup(64, 1024, "bfloat16", "causal",
                             path=tmp_table) == (256, 512)
    assert tile_table.lookup(64, 1024, "bfloat16", "ring",
                             path=tmp_table) == (128, 512)


def test_nearest_seq_and_kind_dominance(tmp_table):
    # seq 6000 is nearer 8192 than 1024 in log space -> the long entry.
    assert tile_table.lookup(64, 6000, "bfloat16", "causal",
                             path=tmp_table) == (512, 1024)
    # kind mismatch dominates geometry: full lookup lands on the one
    # full entry even though causal entries match head_dim/dtype better.
    assert tile_table.lookup(64, 1024, "bfloat16", "full",
                             path=tmp_table) == (128, 256)


def test_missing_table_falls_back_to_default(tmp_path):
    assert tile_table.lookup(64, 1024, "bfloat16", "causal",
                             path=tmp_path / "nope.json") == \
        tile_table.DEFAULT_TILES


def test_corrupt_table_falls_back_loudly_and_once(tmp_path, caplog):
    p = tmp_path / "t.json"
    p.write_text('{"version": 1, "entries": [')          # truncated write
    with caplog.at_level("WARNING", logger="horovod_tpu"):
        for _ in range(3):
            assert tile_table.lookup(64, 1024, "bfloat16", "causal",
                                     path=p) == tile_table.DEFAULT_TILES
    warned = [r for r in caplog.records if "unreadable" in r.getMessage()]
    assert len(warned) == 1 and str(p) in warned[0].getMessage()


def test_tune_tiles_refuses_to_time_the_interpreter(tmp_path, capsys):
    # Off-TPU the kernels are interpreted; the tool used to warn and go
    # on measuring when --out was given.
    import importlib.util
    import os
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "tune_tiles.py")
    spec = importlib.util.spec_from_file_location("hvd_tune_tiles", path)
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    out = tmp_path / "tiles.json"
    assert tool.main(["--quick", "--out", str(out)]) == 2
    assert not out.exists()
    assert "nothing was measured" in capsys.readouterr().err


def test_empty_entries_use_table_default(tmp_path):
    p = tmp_path / "t.json"
    tile_table.save_table({"version": 1, "device": "x",
                           "default": {"block_q": 32, "block_k": 64},
                           "entries": []}, p)
    assert tile_table.lookup(64, 1024, "bfloat16", "causal",
                             path=p) == (32, 64)


def test_bad_kind_raises(tmp_table):
    with pytest.raises(ValueError):
        tile_table.lookup(64, 1024, "bfloat16", "sdpa", path=tmp_table)


def test_record_replaces_and_persists(tmp_table):
    tile_table.record(64, 1024, "bfloat16", "causal", 512, 512,
                      us_per_call=5.0, source="retuned", path=tmp_table)
    assert tile_table.lookup(64, 1024, "bfloat16", "causal",
                             path=tmp_table) == (512, 512)
    data = json.loads(tmp_table.read_text())
    matches = [e for e in data["entries"]
               if (e["head_dim"], e["seq"], e["dtype"], e["kind"]) ==
               (64, 1024, "bfloat16", "causal")]
    assert len(matches) == 1 and matches[0]["source"] == "retuned"


def test_cache_invalidates_on_rewrite(tmp_table):
    assert tile_table.lookup(64, 1024, "bfloat16", "causal",
                             path=tmp_table) == (256, 512)
    tile_table.record(64, 1024, "bfloat16", "causal", 128, 128,
                      path=tmp_table)
    assert tile_table.lookup(64, 1024, "bfloat16", "causal",
                             path=tmp_table) == (128, 128)


def test_record_tolerates_malformed_existing_entries(tmp_path):
    """record() after a sweep must survive entries lookup() tolerates
    (missing keys / wrong types) — no KeyError from the sort."""
    p = tmp_path / "t.json"
    p.write_text(json.dumps({"version": 1, "entries": [
        {"kind": "causal", "dtype": "bfloat16", "head_dim": 64},  # no seq
        {"kind": "full", "dtype": "f32", "head_dim": "x", "seq": "y",
         "block_q": 1, "block_k": 1},
    ]}))
    tile_table.record(64, 1024, "bfloat16", "causal", 256, 512, path=p)
    assert tile_table.lookup(64, 1024, "bfloat16", "causal",
                             path=p) == (256, 512)


def test_shipped_table_is_valid():
    table = tile_table.load_table()
    assert table["entries"], "shipped flash_tiles.json missing or empty"
    for e in table["entries"]:
        assert e["kind"] in tile_table.KINDS
        assert e["block_q"] > 0 and e["block_k"] > 0


def test_flash_attention_consults_table(monkeypatch):
    """flash_attention with no explicit tiles asks the table with the
    right key and uses the answer (lookup_full: fwd + bwd tiles)."""
    import importlib
    fa = importlib.import_module("horovod_tpu.ops.flash_attention")
    calls = []
    real = tile_table.lookup_full

    def spy(head_dim, seq, dtype, kind, path=None):
        calls.append((head_dim, seq, str(dtype), kind))
        return real(head_dim, seq, dtype, kind, path)

    monkeypatch.setattr(tile_table, "lookup_full", spy)
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((1, 64, 2, 16)), jnp.float32)
    out = fa.flash_attention(q, q, q, causal=True)
    assert out.shape == q.shape
    assert calls == [(16, 64, "float32", "causal")]

    # Explicit fwd+bwd tiles bypass the table entirely.
    calls.clear()
    fa.flash_attention(q, q, q, causal=False, block_q=32, block_k=32,
                       block_q_bwd=32, block_k_bwd=32)
    assert calls == []


def test_ring_and_ulysses_consult_table(monkeypatch):
    import jax
    from jax.sharding import PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.ops.ring_flash import ring_flash_attention
    from horovod_tpu.ops.sequence import ulysses_attention

    seen = []
    real = tile_table.lookup
    real_full = tile_table.lookup_full

    def spy(head_dim, seq, dtype, kind, path=None):
        seen.append(kind)
        return real(head_dim, seq, dtype, kind, path)

    def spy_full(head_dim, seq, dtype, kind, path=None):
        seen.append(kind)
        return real_full(head_dim, seq, dtype, kind, path)

    monkeypatch.setattr(tile_table, "lookup", spy)
    monkeypatch.setattr(tile_table, "lookup_full", spy_full)
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((1, 64, 8, 8)), jnp.float32)

    def ring_fn(q, k, v):
        return ring_flash_attention(q, k, v, axis_name="hvd", causal=True)

    def uly_fn(q, k, v):
        return ulysses_attention(q, k, v, axis_name="hvd", causal=True,
                                 impl="flash")

    for fn, kind in ((ring_fn, "ring"), (uly_fn, "causal")):
        seen.clear()
        mapped = hvd.spmd(fn, in_specs=(P(None, "hvd"),) * 3,
                          out_specs=P(None, "hvd"))
        out = mapped(x, x, x)
        jax.block_until_ready(out)
        assert kind in seen, f"{fn.__name__} never consulted the table"


def test_autotune_records_to_table(tmp_path):
    """CPU interpreter-mode tuning exercises the record path end-to-end."""
    from horovod_tpu.autotune import autotune_flash_blocks
    p = tmp_path / "tuned.json"
    best, trials = autotune_flash_blocks(
        (1, 64, 2, 16), dtype="float32", causal=True,
        candidates=[(32, 32), (64, 64)], steps_per_trial=1, chain=1,
        include_backward=False, record=True, record_path=p)
    assert best in trials
    assert tile_table.lookup(16, 64, "float32", "causal", path=p) == best


def test_lookup_full_defaults_bwd_to_fwd(tmp_table):
    # Entries without bwd dims (the whole pre-r5 table): bwd == fwd.
    assert tile_table.lookup_full(
        64, 1024, "bfloat16", "causal",
        path=tmp_table) == (256, 512, 256, 512, 512, 512)


def test_record_and_lookup_bwd_tiles(tmp_table):
    tile_table.record(64, 1024, "bfloat16", "causal", 256, 512,
                      us_per_call=9.0, source="tuned-tpu-fwdbwd",
                      path=tmp_table, block_q_bwd=128, block_k_bwd=1024)
    assert tile_table.lookup_full(
        64, 1024, "bfloat16", "causal",
        path=tmp_table) == (256, 512, 128, 1024, 512, 1024)
    # The fwd-only lookup is unchanged by the bwd dims.
    assert tile_table.lookup(64, 1024, "bfloat16", "causal",
                             path=tmp_table) == (256, 512)
    entry = [e for e in tile_table.load_table(tmp_table)["entries"]
             if e.get("source") == "tuned-tpu-fwdbwd"]
    assert entry and entry[0]["block_q_bwd"] == 128


def test_flash_grads_match_across_bwd_tiles():
    """Distinct backward tiles are a pure performance knob: gradients
    must be identical to the shared-tile backward."""
    import jax
    from horovod_tpu.ops.flash_attention import flash_attention

    rng = np.random.default_rng(2)
    q, k, v = (jnp.asarray(rng.standard_normal((1, 64, 2, 16)),
                           jnp.float32) for _ in range(3))

    def loss(q, k, v, **tiles):
        return jnp.sum(
            flash_attention(q, k, v, causal=True, **tiles) ** 2)

    g_shared = jax.grad(loss, argnums=(0, 1, 2))(
        q, k, v, block_q=32, block_k=32, block_q_bwd=32, block_k_bwd=32)
    g_split = jax.grad(loss, argnums=(0, 1, 2))(
        q, k, v, block_q=32, block_k=32, block_q_bwd=16, block_k_bwd=64)
    for a, b in zip(g_shared, g_split):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


def test_autotune_tune_backward_records_fwdbwd_entry(tmp_path):
    from horovod_tpu.autotune import autotune_flash_blocks
    p = tmp_path / "tuned.json"
    best, trials = autotune_flash_blocks(
        (1, 64, 2, 16), dtype="float32", causal=True,
        candidates=[(32, 32), (64, 64)], steps_per_trial=1, chain=1,
        include_backward=False, tune_backward=True, record=True,
        record_path=p)
    assert len(best) == 4
    assert any(k[0] == "bwd" for k in trials)
    entry = tile_table.load_table(p)["entries"][0]
    assert entry["source"].endswith("-fwdbwd")
    assert (entry["block_q"], entry["block_k"],
            entry["block_q_bwd"], entry["block_k_bwd"]) == best
    assert tile_table.lookup_full(16, 64, "float32", "causal",
                                  path=p)[:4] == best


# --- the causal compute chunk ---------------------------------------------

def test_an_entry_without_a_chunk_yields_the_whole_tile(tmp_table):
    """Every entry nobody swept for a chunk: chunk = block_k, a loop of
    one, for the forward and for the backward's own K tile."""
    assert tile_table.lookup_full(64, 8192, "bfloat16", "causal",
                                  path=tmp_table)[4:] == (1024, 1024)
    # no entry at all: the default tiles, whole
    assert tile_table.lookup_full(
        64, 1024, "bfloat16", "causal",
        path=tmp_table.with_name("none.json"))[4:] == (512, 512)


def test_a_chunk_round_trips_through_record_and_lookup_full(tmp_table):
    tile_table.record(64, 1024, "bfloat16", "causal", 256, 1024,
                      us_per_call=9.0, source="tuned-tpu-fwdbwd",
                      path=tmp_table, block_q_bwd=256, block_k_bwd=1024,
                      chunk=256, chunk_bwd=512)
    assert tile_table.lookup_full(
        64, 1024, "bfloat16", "causal",
        path=tmp_table) == (256, 1024, 256, 1024, 256, 512)
    assert tile_table.lookup(64, 1024, "bfloat16", "causal",
                             path=tmp_table) == (256, 1024)
    entry = [e for e in tile_table.load_table(tmp_table)["entries"]
             if e.get("chunk")]
    assert len(entry) == 1 and entry[0]["chunk_bwd"] == 512


@pytest.mark.parametrize("fields,want", [
    ({"chunk": 256}, (256, 256)),             # the backward shares the K tile
    ({"chunk": 256, "block_k_bwd": 512}, (256, 512)),   # its own K tile
    ({"chunk": 256, "block_k_bwd": 512, "chunk_bwd": 128}, (256, 128)),
    ({"chunk": 300}, (1024, 1024)),           # cuts no whole parts
    ({"chunk": "wide"}, (1024, 1024)),        # malformed: no field
    ({"chunk": 2048}, (1024, 1024)),
])
def test_chunk_defaults_and_malformed_chunks(tmp_path, fields, want):
    p = tmp_path / "t.json"
    entry = {"head_dim": 64, "seq": 1024, "dtype": "bfloat16",
             "kind": "causal", "block_q": 256, "block_k": 1024,
             "us_per_call": 1.0, "source": "test"}
    tile_table.save_table({"version": 1, "device": "test",
                           "entries": [dict(entry, **fields)]}, p)
    assert tile_table.lookup_full(64, 1024, "bfloat16", "causal",
                                  path=p)[4:] == want


def test_the_shipped_gpt2_entry_comes_from_a_fwdbwd_sweep():
    """(head 64, T 1024, bf16, causal) is what both GPT-2 cells of the
    benchmark run 24 times a step: its entry was measured forward and
    backward, and its chunk cuts its K tiles into whole parts."""
    entry = [e for e in tile_table.load_table()["entries"]
             if (e["head_dim"], e["seq"], e["dtype"], e["kind"]) ==
             (64, 1024, "bfloat16", "causal")]
    assert len(entry) == 1
    assert "fwdbwd" in entry[0]["source"]
    bq, bk, bqb, bkb, chunk, chunk_bwd = tile_table.lookup_full(
        64, 1024, "bfloat16", "causal")
    assert chunk == entry[0]["chunk"] < bk and bk % chunk == 0
    assert chunk_bwd < bkb and bkb % chunk_bwd == 0


def test_flash_attention_takes_the_tables_chunk(monkeypatch, tmp_path):
    """The chunk reaches the kernels from the table alone: with the table's
    tiles the call loops inside the tile, with the caller's own tiles it
    runs them whole, and the two agree."""
    import importlib

    import jax
    fa = importlib.import_module("horovod_tpu.ops.flash_attention")
    p = tmp_path / "t.json"
    tile_table.record(16, 64, "float32", "causal", 16, 64, source="test",
                      path=p, chunk=16)
    monkeypatch.setenv("HOROVOD_FLASH_TILE_TABLE", str(p))
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.standard_normal((1, 64, 2, 16)), jnp.float32)

    def loops(**tiles):
        return str(jax.make_jaxpr(jax.grad(lambda q: jnp.sum(
            fa.flash_attention(q, q, q, causal=True, **tiles))))(q)
        ).count("while[")

    # two loops in each of two kernels: the forward, and the dK/dV kernel
    # that also yields dQ
    assert loops() == 4
    assert loops(block_q=16, block_k=128) == 0
    assert loops(block_q=16, block_k=64) == 4    # the table's own tiles
    np.testing.assert_allclose(
        np.asarray(fa.flash_attention(q, q, q, causal=True)),
        np.asarray(fa.flash_attention(q, q, q, causal=True, block_q=16,
                                      block_k=128)), rtol=1e-5, atol=1e-6)
    # not causal: nothing to skip, the whole tile
    assert str(jax.make_jaxpr(lambda q: fa.flash_attention(
        q, q, q, causal=False))(q)).count("while[") == 0


def test_autotune_sweeps_and_records_chunked_candidates(tmp_path):
    from horovod_tpu.autotune import autotune_flash_blocks
    p = tmp_path / "tuned.json"
    best, trials = autotune_flash_blocks(
        (1, 64, 2, 16), dtype="float32", causal=True,
        candidates=[(16, 64, 16), (32, 64, 32)], steps_per_trial=1,
        chain=1, include_backward=False, tune_backward=True, record=True,
        record_path=p)
    assert set(trials) == {(16, 64, 16), (32, 64, 32),
                           ("bwd", 16, 64, 16), ("bwd", 32, 64, 32)}
    assert len(best) == 6
    assert tile_table.lookup_full(16, 64, "float32", "causal",
                                  path=p) == best
    entry = tile_table.load_table(p)["entries"][0]
    assert entry["source"].endswith("-fwdbwd")
    assert (entry["chunk"], entry["chunk_bwd"]) == best[4:]


# --- the VMEM a resident K tile takes (PR 36) ------------------------------

@pytest.mark.parametrize("head,tiles", [
    (64, (256, 8192, 512, 8192, 512, 1024)),
    (256, (256, 8192, 512, 8192, 512, 512)),
])
def test_the_8k_entries_keep_k_resident_forward_and_backward(head, tiles):
    """The two causal shapes the benchmark runs at T 8,192 (the hybrid
    decoder's head 64, latent attention's head 256) come from PR 36's
    forward + backward sweep on the v5e, and both keep the whole key axis
    resident: the forward loops over its chunks, the backward is the one
    kernel that does."""
    fa = _fa()
    entry = tile_table._best_entry(head, 8192, "bfloat16", "causal", None)
    assert (entry["head_dim"], entry["seq"]) == (head, 8192)
    assert entry["source"] == "tuned-v5e-fwdbwd-pr36"
    assert tile_table.lookup_full(head, 8192, "bfloat16", "causal") == tiles
    bq, bk, bqb, bkb, chunk, chunk_bwd = tiles
    assert fa._tiling(8192, 8192, bq, bk, chunk, True, None, d=head,
                      itemsize=2, kernel="fwd") == (bq, 8192, chunk)
    assert fa._tiling(8192, 8192, bqb, bkb, chunk_bwd, True, None, d=head,
                      itemsize=2, kernel="dkv") == (bqb, 8192, chunk_bwd)


def test_the_block_diffusion_entry_keeps_k_resident_too():
    """The one block-diffusion shape the benchmark runs (head 128, 8,192
    positions ``[noisy ; clean]`` in blocks of 4) comes from PR 38's forward
    + backward sweep on the v5e (``tools/tune_tiles.py --fwdbwd --shape
    128x8192``): K resident in chunks of 512 both ways, so the backward is
    one kernel, which asks for the 38.7 MB it needs. A row whose halves
    are no whole chunks takes the entry's chunk as its grid's K tile."""
    fa = _fa()
    entry = tile_table._best_entry(128, 8192, "bfloat16", "block_diffusion",
                                   None)
    assert entry["source"] == "tuned-v5e-fwdbwd-pr38"
    tiles = tile_table.lookup_full(128, 8192, "bfloat16", "block_diffusion")
    assert tiles == (256, 8192, 512, 8192, 512, 512)
    bq, bk, bqb, bkb, chunk, chunk_bwd = tiles
    shape = dict(d=128, itemsize=2)
    assert fa._tiling(8192, 8192, bq, bk, chunk, False, (4096, 4),
                      kernel="fwd", **shape) == (bq, 8192, chunk)
    assert fa._tiling(8192, 8192, bqb, bkb, chunk_bwd, False, (4096, 4),
                      kernel="dkv", **shape) == (bqb, 8192, chunk_bwd)
    need = fa._vmem_need("dkv", bqb, 8192, chunk_bwd, extra="dq", **shape)
    assert fa._VMEM_DEFAULT < need == 38666240 <= fa._VMEM_CAP
    assert fa.bd_tiles(4096, 4, bq, bk, chunk, **shape) == (160, 512)
    assert fa._tiling(8000, 8000, bq, bk, chunk, False, (4000, 4),
                      **shape) == (bq, chunk, None)


def _fa():
    import importlib
    return importlib.import_module("horovod_tpu.ops.flash_attention")


def test_the_gpt2_entry_runs_under_the_compilers_default_vmem():
    """T1's entry (head 64, T 1024: K resident, chunks of 512) needs less
    VMEM than Mosaic gives a kernel that asks for nothing: neither its
    forward nor its one-kernel backward passes ``compiler_params``, so
    their compiled bodies carry no limit of ours."""
    fa = _fa()
    bq, bk, bqb, bkb, chunk, chunk_bwd = tile_table.lookup_full(
        64, 1024, "bfloat16", "causal")
    assert fa._tiling(1024, 1024, bq, bk, chunk, True, None, d=64,
                      itemsize=2, kernel="fwd") == (bq, 1024, chunk)
    assert fa._tiling(1024, 1024, bqb, bkb, chunk_bwd, True, None, d=64,
                      itemsize=2, kernel="dkv") == (bqb, 1024, chunk_bwd)
    for kernel, tq, c, extra in (("fwd", bq, chunk, ""),
                                 ("dkv", bqb, chunk_bwd, "dq")):
        need = fa._vmem_need(kernel, tq, 1024, c, 64, 2, extra=extra)
        assert need <= fa._VMEM_DEFAULT
        assert fa._vmem_params(c, need) == {}
    # a plain grid never asks, whatever it is counted at
    assert fa._vmem_params(None, 10 * fa._VMEM_DEFAULT) == {}
    asked = fa._vmem_params(512, 3 * fa._VMEM_DEFAULT)
    assert asked["compiler_params"].vmem_limit_bytes == 3 * fa._VMEM_DEFAULT


def test_a_key_axis_too_long_for_a_cores_vmem_runs_the_plain_grid():
    """Where a resident K tile (K and V twice, the fp32 dK / dV sums, dK and
    dV twice on their way out) would take more than a v5e core can give,
    ``_tiling`` hands back the grid with the chunk as its K tile: at head
    256 the 8,192 keys of the benchmark's cell fit, 32,768 do not."""
    fa = _fa()
    shape = dict(d=256, itemsize=2, kernel="dkv")
    assert fa._vmem_need("dkv", 512, 8192, 512, 256, 2,
                         extra="dq") <= fa._VMEM_CAP < 128 * 2 ** 20
    assert fa._tiling(8192, 8192, 512, 8192, 512, True, None,
                      **shape) == (512, 8192, 512)
    assert fa._vmem_need("dkv", 512, 32768, 512, 256, 2,
                         extra="dq") > fa._VMEM_CAP
    assert fa._tiling(32768, 32768, 512, 32768, 512, True, None,
                      **shape) == (512, 512, None)
    # what the two 8k entries ask for is inside what a core has
    for head, (_, _, bqb, bkb, _, chunk_bwd) in (
            (h, tile_table.lookup_full(h, 8192, "bfloat16", "causal"))
            for h in (64, 256)):
        asked = fa._vmem_need("dkv", bqb, bkb, chunk_bwd, head, 2,
                              extra="dq")
        assert fa._VMEM_DEFAULT < asked <= fa._VMEM_CAP, head
    # a tracked bias gradient keeps the dK/dV tile whole, and 1024 x 8192
    # scores are more than a core has: the plain grid for both kernels
    assert fa._tiling(8192, 8192, 1024, 8192, 1024, True, None, d=64,
                      itemsize=2, kernel="dkv", per_key=1,
                      track_db=True) == (1024, 1024, None)
    # an entry whose K tile is short of the keys runs its tiles as the
    # grid's, as it always did, if the compiler's default covers them (a
    # plain grid asks for nothing); else the chunk is the grid's K tile
    assert fa._tiling(2048, 2048, 256, 1024, 512, True, None, d=64,
                      itemsize=2) == (256, 1024, None)
    assert fa._tiling(16384, 16384, 256, 8192, 512, True, None, d=256,
                      itemsize=2) == (256, 512, None)
    assert fa._tiling(16384, 16384, 512, 8192, 1024, True, None, d=64,
                      itemsize=2, kernel="dkv") == (512, 1024, None)


# --- the window kind (PR 37) ------------------------------------------------

def test_the_window_kind_round_trips_with_its_window(tmp_table):
    tile_table.record(128, 16384, "bfloat16", "window", 512, 16384,
                      us_per_call=1.0, path=tmp_table, block_q_bwd=256,
                      block_k_bwd=16384, chunk=512, chunk_bwd=1024,
                      window=4096)
    assert tile_table.lookup_full(128, 16384, "bfloat16", "window",
                                  path=tmp_table) == (
        512, 16384, 256, 16384, 512, 1024)
    entry = [e for e in tile_table.load_table(tmp_table)["entries"]
             if e["kind"] == "window"]
    assert len(entry) == 1 and entry[0]["window"] == 4096
    # another kind's entry at the same shape is another entry
    assert tile_table.lookup(128, 16384, "bfloat16", "causal",
                             path=tmp_table) != (512, 16384)


def test_flash_attention_asks_for_the_window_kind(monkeypatch):
    """A windowed call looks its tiles up under ``window``; a window that
    holds every key is the causal mask and asks for that kind."""
    fa = _fa()
    calls = []
    real = tile_table.lookup_full

    def spy(head_dim, seq, dtype, kind, path=None):
        calls.append((head_dim, seq, str(dtype), kind))
        return real(head_dim, seq, dtype, kind, path)

    monkeypatch.setattr(tile_table, "lookup_full", spy)
    q = jnp.zeros((1, 64, 2, 16), jnp.float32)
    jax.make_jaxpr(lambda q: fa.flash_attention(q, q, q, causal=True,
                                                window=16))(q)
    jax.make_jaxpr(lambda q: fa.flash_attention(q, q, q, causal=True,
                                                window=64))(q)
    assert calls == [(16, 64, "float32", "window"),
                     (16, 64, "float32", "causal")]


def test_autotune_sweeps_and_records_under_a_window(tmp_path):
    from horovod_tpu.autotune import autotune_flash_blocks
    p = tmp_path / "tuned.json"
    best, trials = autotune_flash_blocks(
        (1, 64, 2, 16), dtype="float32", causal=True, window=24,
        candidates=[(16, 16), (16, 64, 16)], steps_per_trial=1, chain=1,
        include_backward=False, tune_backward=True, record=True,
        record_kind="window", record_path=p)
    assert set(trials) == {(16, 16), (16, 64, 16), ("bwd", 16, 16),
                           ("bwd", 16, 64, 16)}
    entry = tile_table.load_table(p)["entries"][0]
    assert (entry["kind"], entry["window"]) == ("window", 24)
    assert tile_table.lookup_full(16, 64, "float32", "window",
                                  path=p)[:4] == best[:4]


@pytest.mark.parametrize("kind", ["causal", "window"])
def test_the_16k_entries_keep_k_resident_forward_and_backward(kind):
    """The two shapes the window/global-attention cell runs at head 128 and
    T 16,384 come from this PR's forward + backward sweep on the v5e; both
    keep the whole key axis resident within what a core has, so the
    backward is one kernel, and the window's loop visits under 30 % of the
    pairs where the causal one visits over half."""
    fa = _fa()
    entry = tile_table._best_entry(128, 16384, "bfloat16", kind, None)
    assert (entry["head_dim"], entry["seq"], entry["kind"]) == (
        128, 16384, kind)
    assert entry["source"] == "tuned-v5e-fwdbwd-pr37"
    assert entry.get("window") == (4096 if kind == "window" else None)
    bq, bk, bqb, bkb, chunk, chunk_bwd = tile_table.lookup_full(
        128, 16384, "bfloat16", kind)
    assert fa._tiling(16384, 16384, bq, bk, chunk, True, None, d=128,
                      itemsize=2, kernel="fwd") == (bq, 16384, chunk)
    assert fa._tiling(16384, 16384, bqb, bkb, chunk_bwd, True, None, d=128,
                      itemsize=2, kernel="dkv") == (bqb, 16384, chunk_bwd)
    asked = fa._vmem_need("dkv", bqb, bkb, chunk_bwd, 128, 2, extra="dq")
    assert fa._VMEM_DEFAULT < asked <= fa._VMEM_CAP
    seen, of = fa.causal_tiles(16384, bq, bk, chunk, d=128, itemsize=2)
    assert 50 < 100 * seen / of < 60
    if kind == "window":
        seen, of = fa.window_tiles(16384, 4096, bq, bk, chunk, d=128,
                                   itemsize=2)
        assert 21.9 < 100 * seen / of < 30
