"""Tile-table lookup wiring (VERDICT r3 item 2).

Upstream analogue: horovod/runner/autotune ships tuned fusion parameters;
here the tuned artifact is the checked-in flash-tile table that
``flash_attention``/``ring_flash_attention``/``ulysses_attention`` consult
by default. CPU tests pin the lookup wiring; on-chip numbers regenerate the
data via ``tools/tune_tiles.py``.
"""

import json

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
    assert tile_table.lookup_full(64, 1024, "bfloat16", "causal",
                                  path=tmp_table) == (256, 512, 256, 512)


def test_record_and_lookup_bwd_tiles(tmp_table):
    tile_table.record(64, 1024, "bfloat16", "causal", 256, 512,
                      us_per_call=9.0, source="tuned-tpu-fwdbwd",
                      path=tmp_table, block_q_bwd=128, block_k_bwd=1024)
    assert tile_table.lookup_full(64, 1024, "bfloat16", "causal",
                                  path=tmp_table) == (256, 512, 128, 1024)
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
                                  path=p) == best
