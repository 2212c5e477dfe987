"""Model parallelism over the named ``dp x mp`` mesh (``HOROVOD_MESH``).

One subsystem, three consumers:

* **GSPMD training** — :func:`mp_partition_rules` maps the model zoo's
  Megatron-style ``"tp"`` rule sets onto the runtime ``"mp"`` axis of
  :func:`horovod_tpu.core.mesh2d`, so annotate-and-jit training shards
  attention/MLP weights with one psum per block (``parallel/sharding.py``
  does the placement, XLA inserts the collectives).
* **ZeRO-2/3 training** — the ``zero2_*``/``zero3_*`` surface: gradients
  reduce-scatter to their owner's flat chunk, parameters all-gather
  just-in-time per block. ZeRO-3 is ``parallel/fsdp.py``'s machinery
  re-exported under the one sharding story (fsdp IS ZeRO-3; the fsdp
  names stay as the engine room), extended with a ``wire=`` option so
  the heavy parameter all-gathers ride the int8/fp8 EQuARX formats of
  ``ops/quantized.py`` (lossy — the exact fp32 path is the default).
* **Tensor-parallel serving** — :func:`split_params` slices GPT-2/Llama
  weights head/vocab/ff-aligned per mp rank, and
  :func:`tp_decode_step` / :func:`tp_decode_verify_step` are collective-
  matmul twins of the ``models/generate.py`` registry steps: column-
  parallel qkv/fc, row-parallel out/proj closed by ``lax.psum``,
  vocab-parallel embedding + logits head closed by a tiled
  ``lax.all_gather``. The serving engine swaps these in under
  ``shard_map`` (:func:`wrap_spmd`) so the whole decode program — paged
  cache, copy-on-write, spec-verify scan — stays ONE jitted program and
  ``decode_compiles == 1`` survives mp > 1.

Numerical contract: replicated activations stay in bitwise lockstep
across mp ranks (psum delivers identical sums everywhere), column-
parallel matmuls and the vocab-parallel embedding are bit-exact against
the replicated lowering, and row-parallel psums differ from the
replicated matmul only by fp reduction order — inside the band
:func:`models.generate.greedy_token`'s tolerance tie-break absorbs,
which is what keeps engine tokens identical to offline ``generate()``.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from horovod_tpu.models.generate import (
    _as_cache, _attend_cached, _layernorm, _rmsnorm, _rope_one,
    decode_family, greedy_token,
)
from horovod_tpu.ops.quantized import dequantize_blocks, quantize_blocks
from horovod_tpu.parallel.fsdp import (
    _HashableStruct, _as_struct, flat_size, fsdp_adamw, fsdp_apply,
    fsdp_scan_blocks, fsdp_shard_params, stack_layer_shards,
)
from horovod_tpu.parallel.sharding import PartitionRules
from horovod_tpu.optimizer_sharded import (_adamw_chunk_update, _flatten,
                                           _unflatten, ShardedAdamWState)

__all__ = [
    "MP_AXIS", "validate_tp", "mp_partition_rules",
    "split_params", "merge_params", "param_bytes",
    "tp_decode_step", "tp_decode_verify_step", "wrap_spmd",
    "mp_stack", "mp_broadcast", "mp_fetch",
    "gather_shard",
    "zero3_shard_params", "zero3_apply", "zero3_scan_blocks",
    "zero3_stack_layer_shards", "zero3_adamw",
    "zero2_grad_shard", "zero2_update",
]

#: name of the model-parallel axis on core.mesh2d()
MP_AXIS = "mp"

# ZeRO-3 is fsdp under the one sharding story: same flat-chunk layout,
# same gather-is-the-remat custom VJP, same no-update-allgather AdamW.
zero3_shard_params = fsdp_shard_params
zero3_scan_blocks = fsdp_scan_blocks
zero3_stack_layer_shards = stack_layer_shards
zero3_adamw = fsdp_adamw


# ---------------------------------------------------------------------------
# validation + partition rules
# ---------------------------------------------------------------------------

def validate_tp(cfg, mp: int) -> None:
    """Raise unless ``cfg`` splits cleanly over ``mp`` tensor-parallel
    ranks: heads, kv heads, ff width and vocab must all divide (the
    splits are head/vocab-aligned, not element-striped)."""
    fam = decode_family(cfg)
    if fam.name not in ("gpt2", "llama"):
        raise NotImplementedError(
            f"tensor parallelism is implemented for the gpt2/llama "
            f"families, not {fam.name!r}")
    if mp < 1:
        raise ValueError(f"mp degree must be >= 1, got {mp}")
    if cfg.num_heads % mp:
        raise ValueError(
            f"mp={mp} must divide num_heads={cfg.num_heads} "
            f"(attention splits by whole heads)")
    kv = fam.kv_heads(cfg)
    if kv % mp:
        raise ValueError(
            f"mp={mp} must divide num_kv_heads={kv} "
            f"(the KV pool splits by whole kv heads)")
    if cfg.vocab_size % mp:
        raise ValueError(
            f"mp={mp} must divide vocab_size={cfg.vocab_size} "
            f"(the embedding/logits head splits by vocab rows)")
    d_ff = getattr(cfg, "d_ff", 4 * cfg.d_model)
    if d_ff % mp:
        raise ValueError(
            f"mp={mp} must divide the MLP width {d_ff}")


def mp_partition_rules(cfg, rules: Optional[str] = None) -> PartitionRules:
    """The model family's Megatron rule set rebased onto the runtime
    ``"mp"`` axis — what GSPMD-annotated training shards over
    ``core.mesh2d()``.

    ``rules`` is the ``HOROVOD_MP_RULES`` mode (default: the config
    knob): ``"auto"`` and ``"megatron"`` both resolve to the family's
    column/row split (auto exists so future families can pick different
    defaults); ``"off"`` replicates everything — the debugging escape
    hatch that keeps the mesh but removes the sharding.
    """
    if rules is None:
        from horovod_tpu.config import get_config
        rules = get_config().mp_rules
    if rules == "off":
        return PartitionRules([])
    fam = decode_family(cfg)
    if fam.name == "gpt2":
        from horovod_tpu.models.gpt2 import partition_rules as base_rules
    elif fam.name == "llama":
        from horovod_tpu.models.llama import partition_rules as base_rules
    else:
        raise NotImplementedError(
            f"no mp rule set for the {fam.name!r} family")
    out = []
    for pat, spec in base_rules().rules:
        out.append((pat.pattern,
                    P(*(MP_AXIS if s == "tp" else s for s in spec))))
    return PartitionRules(out)


# ---------------------------------------------------------------------------
# explicit weight splitting (the serving engine's layout)
# ---------------------------------------------------------------------------

def param_bytes(tree) -> int:
    """Total bytes of a parameter pytree (per-rank footprint metric)."""
    return sum(np.asarray(l).nbytes
               for l in jax.tree_util.tree_leaves(tree))


def _rows(a, n, r):
    a = np.asarray(a)
    c = a.shape[0] // n
    return a[r * c:(r + 1) * c]


def _cols(a, n, r):
    a = np.asarray(a)
    c = a.shape[1] // n
    return a[:, r * c:(r + 1) * c]


def _split_gpt2(cfg, params, mp, r):
    H, hd = cfg.num_heads, cfg.d_model // cfg.num_heads
    Hl = H // mp
    out = {"wte": _rows(params["wte"], mp, r),
           "wpe": np.asarray(params["wpe"]),
           "ln_f": jax.tree_util.tree_map(np.asarray, params["ln_f"])}
    for i in range(cfg.num_layers):
        p = params[f"h{i}"]
        D = cfg.d_model
        # The packed qkv kernel is (D, [q|k|v]) — a contiguous column
        # slice would mix q into k. Reshape to (D, 3, H, hd), slice whole
        # heads, flatten back: the local (D, 3*Hl*hd) keeps the packing
        # convention, so the step's jnp.split(qkv, 3) stays valid.
        qkv_k = np.asarray(p["attn"]["qkv"]["kernel"]).reshape(D, 3, H, hd)
        qkv_b = np.asarray(p["attn"]["qkv"]["bias"]).reshape(3, H, hd)
        out_k = np.asarray(p["attn"]["out"]["kernel"]).reshape(H, hd, D)
        out[f"h{i}"] = {
            "ln1": jax.tree_util.tree_map(np.asarray, p["ln1"]),
            "ln2": jax.tree_util.tree_map(np.asarray, p["ln2"]),
            "attn": {
                "qkv": {
                    "kernel": qkv_k[:, :, r * Hl:(r + 1) * Hl]
                    .reshape(D, 3 * Hl * hd),
                    "bias": qkv_b[:, r * Hl:(r + 1) * Hl].reshape(-1)},
                "out": {
                    # Row-parallel: slice input heads; the bias is NOT
                    # split — it is added once, after the psum.
                    "kernel": out_k[r * Hl:(r + 1) * Hl]
                    .reshape(Hl * hd, D),
                    "bias": np.asarray(p["attn"]["out"]["bias"])}},
            "mlp": {
                "fc": {"kernel": _cols(p["mlp"]["fc"]["kernel"], mp, r),
                       "bias": _rows(p["mlp"]["fc"]["bias"], mp, r)},
                "proj": {"kernel": _rows(p["mlp"]["proj"]["kernel"],
                                         mp, r),
                         "bias": np.asarray(p["mlp"]["proj"]["bias"])}},
        }
    return out


def _split_llama(cfg, params, mp, r):
    H, Hkv = cfg.num_heads, cfg.num_kv_heads
    hd = cfg.d_model // H
    Hl, Hkvl = H // mp, Hkv // mp
    out = {"wte": _rows(params["wte"], mp, r),
           "lm_head": _rows(params["lm_head"], mp, r),
           "norm_f": jax.tree_util.tree_map(np.asarray, params["norm_f"])}
    for i in range(cfg.num_layers):
        p = params[f"h{i}"]
        wo = np.asarray(p["attn"]["wo"]["kernel"])
        out[f"h{i}"] = {
            "norm_attn": jax.tree_util.tree_map(np.asarray,
                                                p["norm_attn"]),
            "norm_mlp": jax.tree_util.tree_map(np.asarray, p["norm_mlp"]),
            "attn": {
                # Kernels are head-major (feature j = head j//hd), so a
                # contiguous column run of Hl*hd IS a whole-head slice.
                "wq": {"kernel": np.asarray(p["attn"]["wq"]["kernel"])
                       [:, r * Hl * hd:(r + 1) * Hl * hd]},
                "wk": {"kernel": np.asarray(p["attn"]["wk"]["kernel"])
                       [:, r * Hkvl * hd:(r + 1) * Hkvl * hd]},
                "wv": {"kernel": np.asarray(p["attn"]["wv"]["kernel"])
                       [:, r * Hkvl * hd:(r + 1) * Hkvl * hd]},
                "wo": {"kernel": wo[r * Hl * hd:(r + 1) * Hl * hd]}},
            "mlp": {
                "gate": {"kernel": _cols(p["mlp"]["gate"]["kernel"],
                                         mp, r)},
                "up": {"kernel": _cols(p["mlp"]["up"]["kernel"], mp, r)},
                "down": {"kernel": _rows(p["mlp"]["down"]["kernel"],
                                         mp, r)}},
        }
    return out


def split_params(cfg, params, mp: int, rank: int):
    """Rank ``rank``'s 1/mp slice of a full parameter tree (host numpy;
    Megatron layout — see the module docstring for which axis each
    kernel splits on). ``mp == 1`` returns the tree unsliced."""
    validate_tp(cfg, mp)
    if not 0 <= rank < mp:
        raise ValueError(f"rank {rank} outside the mp={mp} axis")
    if mp == 1:
        return jax.tree_util.tree_map(np.asarray, params)
    fam = decode_family(cfg)
    if fam.name == "gpt2":
        return _split_gpt2(cfg, params, mp, rank)
    return _split_llama(cfg, params, mp, rank)


def merge_params(cfg, parts):
    """Inverse of :func:`split_params`: the full tree from all ``mp``
    rank slices in rank order (checkpoint resharding onto a different
    mp degree re-splits the merged tree)."""
    mp = len(parts)
    if mp == 1:
        return jax.tree_util.tree_map(np.asarray, parts[0])
    fam = decode_family(cfg)
    H, hd = cfg.num_heads, cfg.d_model // cfg.num_heads
    Hl = H // mp

    def cat(path_leaves, axis):
        return np.concatenate([np.asarray(l) for l in path_leaves], axis)

    if fam.name == "gpt2":
        D = cfg.d_model
        out = {"wte": cat([p["wte"] for p in parts], 0),
               "wpe": np.asarray(parts[0]["wpe"]),
               "ln_f": jax.tree_util.tree_map(np.asarray,
                                              parts[0]["ln_f"])}
        for i in range(cfg.num_layers):
            ls = [p[f"h{i}"] for p in parts]
            qkv_k = cat([l["attn"]["qkv"]["kernel"]
                         .reshape(D, 3, Hl, hd) for l in ls], 2)
            qkv_b = cat([l["attn"]["qkv"]["bias"].reshape(3, Hl, hd)
                         for l in ls], 1)
            out_k = cat([l["attn"]["out"]["kernel"].reshape(Hl, hd, D)
                         for l in ls], 0)
            out[f"h{i}"] = {
                "ln1": jax.tree_util.tree_map(np.asarray, ls[0]["ln1"]),
                "ln2": jax.tree_util.tree_map(np.asarray, ls[0]["ln2"]),
                "attn": {
                    "qkv": {"kernel": qkv_k.reshape(D, 3 * H * hd),
                            "bias": qkv_b.reshape(-1)},
                    "out": {"kernel": out_k.reshape(H * hd, D),
                            "bias": np.asarray(
                                ls[0]["attn"]["out"]["bias"])}},
                "mlp": {
                    "fc": {"kernel": cat(
                        [l["mlp"]["fc"]["kernel"] for l in ls], 1),
                        "bias": cat(
                            [l["mlp"]["fc"]["bias"] for l in ls], 0)},
                    "proj": {"kernel": cat(
                        [l["mlp"]["proj"]["kernel"] for l in ls], 0),
                        "bias": np.asarray(
                            ls[0]["mlp"]["proj"]["bias"])}},
            }
        return out
    out = {"wte": cat([p["wte"] for p in parts], 0),
           "lm_head": cat([p["lm_head"] for p in parts], 0),
           "norm_f": jax.tree_util.tree_map(np.asarray,
                                            parts[0]["norm_f"])}
    for i in range(cfg.num_layers):
        ls = [p[f"h{i}"] for p in parts]
        out[f"h{i}"] = {
            "norm_attn": jax.tree_util.tree_map(np.asarray,
                                                ls[0]["norm_attn"]),
            "norm_mlp": jax.tree_util.tree_map(np.asarray,
                                               ls[0]["norm_mlp"]),
            "attn": {k: {"kernel": cat(
                [l["attn"][k]["kernel"] for l in ls],
                0 if k == "wo" else 1)} for k in ("wq", "wk", "wv", "wo")},
            "mlp": {k: {"kernel": cat(
                [l["mlp"][k]["kernel"] for l in ls],
                0 if k == "down" else 1)} for k in ("gate", "up", "down")},
        }
    return out


# ---------------------------------------------------------------------------
# placing mp-stacked arrays on the 2-D mesh
# ---------------------------------------------------------------------------

def _mp_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P(MP_AXIS))


def _my_mp_coords(mesh: Mesh):
    """mp coordinates whose device column is addressable by this
    process (engine tp runs dp == 1, so row 0 is the whole mp axis)."""
    pidx = jax.process_index()
    col = list(np.asarray(mesh.devices)[0])
    return [r for r, d in enumerate(col) if d.process_index == pidx]


def mp_stack(fn: Callable[[int], Any], mesh: Mesh):
    """Build global ``(mp, *local)`` arrays over ``mesh``'s mp axis, row
    ``r`` being ``fn(r)``'s leaves. Single-process: every row is built
    and device_put sharded. Multi-process: each process builds only the
    rows its devices own (``jax.make_array_from_process_local_data`` —
    the same bridge the eager collectives use), so no host ever
    materializes another rank's slice."""
    mp = mesh.shape[MP_AXIS]
    shd = _mp_sharding(mesh)
    if jax.process_count() == 1:
        rows = [fn(r) for r in range(mp)]
        return jax.tree_util.tree_map(
            lambda *xs: jax.device_put(
                np.stack([np.asarray(x) for x in xs]), shd), *rows)
    mine = _my_mp_coords(mesh)
    rows = {r: fn(r) for r in mine}
    flat0, treedef = jax.tree_util.tree_flatten(rows[mine[0]])
    flat = {r: jax.tree_util.tree_leaves(rows[r]) for r in mine}
    out = []
    for i in range(len(flat0)):
        local = np.stack([np.asarray(flat[r][i]) for r in mine])
        gshape = (mp,) + local.shape[1:]
        out.append(jax.make_array_from_process_local_data(
            shd, local, gshape))
    return jax.tree_util.tree_unflatten(treedef, out)


def mp_broadcast(tree, mesh: Mesh):
    """Replicate host value(s) into the ``(mp, *shape)`` stacked layout
    — every row identical (per-step engine inputs: token/position
    vectors every process computed in lockstep)."""
    return mp_stack(lambda r: tree, mesh)


def mp_fetch(x) -> np.ndarray:
    """One row of an mp-stacked global array back to host. Correct for
    replicated-content outputs (every row identical — greedy picks and
    gathered logits), where any addressable row is THE value."""
    shard = x.addressable_shards[0]
    return np.asarray(shard.data)[0]


def wrap_spmd(body: Callable, mesh: Mesh) -> Callable:
    """Lift an engine program written against LOCAL shapes into the
    mp-stacked global layout: every argument/result leaf is ``(mp,
    *local)`` sharded ``P("mp")``; the shard_map body peels the unit
    leading dim, runs ``body`` (whose tp collectives see the ``"mp"``
    axis), and restacks. ``check_vma=False`` for the same reason as
    ``hvd.spmd`` — the tp psums are manual, not replication-tracked."""

    def inner(*args):
        local = jax.tree_util.tree_map(lambda a: a[0], args)
        out = body(*local)
        return jax.tree_util.tree_map(lambda a: a[None], out)

    mapped = jax.shard_map(inner, mesh=mesh, in_specs=P(MP_AXIS),
                           out_specs=P(MP_AXIS), check_vma=False)

    def wrapped(*args):
        return mapped(*args)

    return wrapped


# ---------------------------------------------------------------------------
# tensor-parallel decode steps (collective-matmul twins of the
# models/generate.py registry steps — same math, 1/mp of every weight)
# ---------------------------------------------------------------------------

def _vocab_parallel_embed(wte, tok, axis):
    """Embedding lookup over a vocab-row-sliced table: each rank looks
    up the ids it owns, zeros the rest, and one psum assembles the full
    rows — bit-exact vs the replicated lookup (x + 0 == x in fp)."""
    vl = wte.shape[0]
    lo = lax.axis_index(axis) * vl
    loc = jnp.clip(tok - lo, 0, vl - 1)
    e = wte[loc]
    ok = ((tok >= lo) & (tok < lo + vl))[..., None]
    return lax.psum(jnp.where(ok, e, jnp.zeros_like(e)), axis)


def _tp_gpt2_step(cfg, axis, params, cache, tok, idx):
    """:func:`models.generate._gpt2_step` with 1/mp weights: column-
    parallel qkv/fc (whole heads / whole columns — exact per element),
    row-parallel out/proj closed by one psum per pair (Megatron), the
    replicated bias added once AFTER the psum, and the tied logits head
    assembled by a tiled vocab all-gather."""
    cache, raw = _as_cache(cache)
    dt = cfg.dtype
    mp = lax.psum(1, axis)                      # static axis size
    Hl = cfg.num_heads // mp
    hd = cfg.d_model // cfg.num_heads
    x = _vocab_parallel_embed(params["wte"], tok, axis).astype(dt) \
        + params["wpe"][idx].astype(dt)
    for i in range(cfg.num_layers):
        p = params[f"h{i}"]
        h = _layernorm(x, p["ln1"], cfg.ln_eps).astype(dt)
        qkv = h @ p["attn"]["qkv"]["kernel"].astype(dt) \
            + p["attn"]["qkv"]["bias"].astype(dt)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        cache, ck, cv = cache.update(i, k.reshape(-1, Hl, hd),
                                     v.reshape(-1, Hl, hd), idx)
        o = _attend_cached(q.reshape(-1, Hl, hd), ck, cv, idx, hd ** -0.5)
        x = x + (lax.psum(o.reshape(-1, Hl * hd)
                          @ p["attn"]["out"]["kernel"].astype(dt), axis)
                 + p["attn"]["out"]["bias"].astype(dt))
        h = _layernorm(x, p["ln2"], cfg.ln_eps).astype(dt)
        h = jax.nn.gelu(h @ p["mlp"]["fc"]["kernel"].astype(dt)
                        + p["mlp"]["fc"]["bias"].astype(dt))
        x = x + (lax.psum(h @ p["mlp"]["proj"]["kernel"].astype(dt), axis)
                 + p["mlp"]["proj"]["bias"].astype(dt))
    x = _layernorm(x, params["ln_f"], cfg.ln_eps)        # fp32
    logits = x @ params["wte"].T                         # (B, V/mp) fp32
    return (cache.layers if raw else cache), \
        lax.all_gather(logits, axis, axis=1, tiled=True)


def _tp_llama_step(cfg, axis, params, cache, tok, idx):
    cache, raw = _as_cache(cache)
    dt = cfg.dtype
    mp = lax.psum(1, axis)
    Hl = cfg.num_heads // mp
    Hkvl = cfg.num_kv_heads // mp
    hd = cfg.d_model // cfg.num_heads
    x = _vocab_parallel_embed(params["wte"], tok, axis).astype(dt)
    for i in range(cfg.num_layers):
        p = params[f"h{i}"]
        h = _rmsnorm(x, p["norm_attn"], cfg.rms_eps)
        q = (h @ p["attn"]["wq"]["kernel"].astype(dt)).reshape(-1, Hl, hd)
        k = (h @ p["attn"]["wk"]["kernel"].astype(dt)) \
            .reshape(-1, Hkvl, hd)
        v = (h @ p["attn"]["wv"]["kernel"].astype(dt)) \
            .reshape(-1, Hkvl, hd)
        # RoPE is per-head (position x head_dim only), so it commutes
        # with the head split; GQA grouping survives because Hl/Hkvl ==
        # H/Hkv — the local query heads of kv head j are exactly its
        # global group.
        q = _rope_one(q, idx, cfg.rope_theta)
        k = _rope_one(k, idx, cfg.rope_theta)
        cache, ck, cv = cache.update(i, k, v, idx)
        o = _attend_cached(q, ck, cv, idx, hd ** -0.5)
        x = x + lax.psum(o.reshape(-1, Hl * hd)
                         @ p["attn"]["wo"]["kernel"].astype(dt), axis)
        h = _rmsnorm(x, p["norm_mlp"], cfg.rms_eps)
        g = jax.nn.silu(h @ p["mlp"]["gate"]["kernel"].astype(dt))
        u = h @ p["mlp"]["up"]["kernel"].astype(dt)
        x = x + lax.psum((g * u) @ p["mlp"]["down"]["kernel"].astype(dt),
                         axis)
    x = _rmsnorm(x, params["norm_f"], cfg.rms_eps)
    logits = x.astype(jnp.float32) @ params["lm_head"].T
    return (cache.layers if raw else cache), \
        lax.all_gather(logits, axis, axis=1, tiled=True)


_TP_STEPS = {"gpt2": _tp_gpt2_step, "llama": _tp_llama_step}


def tp_decode_step(cfg, axis: str = MP_AXIS):
    """Tensor-parallel ``(params, cache, tok, pos, extras=None) ->
    (cache, logits)``: the registry decode step's signature over 1/mp
    weights and a 1/mp-kv-heads cache. Call inside shard_map with
    ``axis`` in scope; logits come back FULL (vocab-gathered), so every
    consumer of the replicated step — verify scan, greedy tie-break,
    host sampling — works unchanged."""
    fam = decode_family(cfg)
    fam.validate(cfg)
    impl = _TP_STEPS.get(fam.name)
    if impl is None:
        raise NotImplementedError(
            f"tensor-parallel decode is implemented for gpt2/llama, "
            f"not {fam.name!r}")

    def step(params, cache, tok, pos, extras=None):
        return impl(cfg, axis, params, cache, tok, pos)

    return step


def tp_decode_verify_step(cfg, axis: str = MP_AXIS):
    """Tensor-parallel twin of :func:`models.generate
    .decode_verify_step` — the same K-step scan (one program for any K,
    K == 1 is the classic decode) over :func:`tp_decode_step`."""
    step = tp_decode_step(cfg, axis)
    vocab = cfg.vocab_size

    def verify(params, cache, tok_seq, pos0, counts=None, extras=None,
               mask_fn=None):
        pos0 = jnp.asarray(pos0, jnp.int32)
        first0 = jnp.zeros((tok_seq.shape[1], vocab), jnp.float32)

        def body(carry, inp):
            cache, first = carry
            tok, j = inp
            if mask_fn is not None and counts is not None:
                cache = mask_fn(cache, j < counts)
            cache, logits = step(params, cache, tok, pos0 + j, extras)
            first = jnp.where(j == 0, logits.astype(jnp.float32), first)
            return (cache, first), greedy_token(logits).astype(jnp.int32)

        K = tok_seq.shape[0]
        (cache, first), greedy = jax.lax.scan(
            body, (cache, first0),
            (tok_seq, jnp.arange(K, dtype=jnp.int32)))
        return cache, first, greedy

    return verify


# ---------------------------------------------------------------------------
# ZeRO-2/3: sharded optimizer states + just-in-time parameter gathers
# ---------------------------------------------------------------------------

def gather_shard(shard: jnp.ndarray, axis_name: Optional[str] = None,
                 wire: Optional[str] = None) -> jnp.ndarray:
    """``(c,)`` flat shard -> ``(n*c,)`` full vector over ``axis_name``,
    optionally riding a reduced-precision wire: ``None``/``"fp32"`` is
    the exact tiled all-gather, ``"bf16"`` casts the payload around the
    collective, ``"int8"``/``"fp8"`` ship the EQuARX 1-byte format with
    per-256-value fp32 scales (``ops/quantized.py``) — half/quarter the
    gather bytes at a bounded rounding cost (LOSSY: bit-exact pins must
    stay on the default wire)."""
    from horovod_tpu import core
    ax = axis_name or core.axis_name()
    if not wire or wire == "fp32":
        return lax.all_gather(shard, ax, tiled=True)
    if wire == "bf16":
        g = lax.all_gather(shard.astype(jnp.bfloat16), ax, tiled=True)
        return g.astype(shard.dtype)
    if wire not in ("int8", "fp8"):
        raise ValueError(f"gather_shard wire={wire!r}: expected fp32, "
                         f"bf16, int8 or fp8")
    q, scale = quantize_blocks(shard.astype(jnp.float32), wire=wire)
    # Per-rank rows (NOT tiled): each rank's ragged scale tail must stay
    # aligned with its own payload through the dequantize.
    gq = lax.all_gather(q, ax)
    gs = lax.all_gather(scale, ax)
    return dequantize_blocks(gq, gs).reshape(-1).astype(shard.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 4, 5))
def _zero3_call_wire(block_fn, template, shard, x, axis_name, wire):
    full = gather_shard(shard, axis_name, wire)
    return block_fn(_unflatten(full[:flat_size(template.tree)],
                               template.tree), x)


def _zero3_wire_fwd(block_fn, template, shard, x, axis_name, wire):
    return _zero3_call_wire(block_fn, template, shard, x, axis_name,
                            wire), (shard, x)


def _zero3_wire_bwd(block_fn, template, axis_name, wire, res, ct):
    shard, x = res
    n = lax.psum(1, axis_name)

    def run_full(full_flat, x_):
        L = flat_size(template.tree)
        return block_fn(_unflatten(full_flat[:L], template.tree), x_)

    # Gather-is-the-remat, on the same wire the forward used (so the
    # recompute sees the SAME dequantized weights the forward saw);
    # gradients reduce-scatter in full precision — ZeRO quantizes the
    # parameter traffic, never the gradient owners' accumulation.
    full = gather_shard(shard, axis_name, wire)
    _, vjp = jax.vjp(run_full, full, x)
    g_full, g_x = vjp(ct)
    g_shard = lax.psum_scatter(g_full, axis_name, scatter_dimension=0,
                               tiled=True) / n
    return g_shard, g_x


_zero3_call_wire.defvjp(_zero3_wire_fwd, _zero3_wire_bwd)


def zero3_apply(block_fn: Callable, template: Any, shard: jnp.ndarray,
                x, axis_name: Optional[str] = None,
                wire: Optional[str] = None):
    """ZeRO-3 block apply: :func:`parallel.fsdp.fsdp_apply` (the exact
    fp32 path) unless ``wire`` picks a reduced-precision gather — then
    the just-in-time parameter all-gathers ride the bf16/int8/fp8 wire
    (lossy; the gradient reduce-scatter stays full precision)."""
    from horovod_tpu import core
    ax = axis_name or core.axis_name()
    if not wire or wire == "fp32":
        return fsdp_apply(block_fn, template, shard, x, axis_name=ax)
    return _zero3_call_wire(block_fn, _HashableStruct(_as_struct(template)),
                            shard, x, ax, wire)


def zero2_grad_shard(grads, axis_name: Optional[str] = None
                     ) -> jnp.ndarray:
    """ZeRO-2 gradient ownership: the full (replicated-per-rank) grads
    pytree -> this rank's mean ``(c,)`` chunk via ONE fused
    reduce-scatter — the data-parallel sync and the sharding are the
    same collective (call inside shard_map)."""
    from horovod_tpu import core
    ax = axis_name or core.axis_name()
    n = lax.psum(1, ax)
    flat = _flatten(grads)
    c = -(-flat.shape[0] // n)
    flat = jnp.pad(flat, (0, n * c - flat.shape[0]))
    return lax.psum_scatter(flat, ax, scatter_dimension=0,
                            tiled=True) / n


def zero2_update(params, g_shard: jnp.ndarray, state: ShardedAdamWState,
                 *, learning_rate: float, b1: float = 0.9,
                 b2: float = 0.999, eps: float = 1e-8,
                 weight_decay: float = 0.0,
                 axis_name: Optional[str] = None,
                 wire: Optional[str] = None):
    """One ZeRO-2 step: AdamW on this rank's owned chunk (state stays
    ``(c,)`` forever), then ONE all-gather of the *update* — optionally
    on the reduced-precision wire — applied to the still-replicated
    parameters. Returns ``(new_params, new_state)``.

    This is the ZeRO stage between ``sharded_adamw`` (ZeRO-1, eager)
    and :func:`zero3_apply` (params sharded too): parameters replicated,
    gradients + optimizer state owned. ``state`` is a per-rank slice of
    ``zero3_adamw(...).init``'s layout (shard its leaves with
    ``P(axis)`` like the fsdp path does).
    """
    from horovod_tpu import core
    ax = axis_name or core.axis_name()
    n = lax.psum(1, ax)
    r = lax.axis_index(ax)
    flat_p = _flatten(params)
    c = g_shard.shape[0]
    p_pad = jnp.pad(flat_p, (0, n * c - flat_p.shape[0]))
    p_shard = lax.dynamic_slice_in_dim(p_pad, r * c, c)
    upd, (step, mu, nu) = _adamw_chunk_update(
        g_shard, state, p_shard, learning_rate, b1, b2, eps, weight_decay)
    full_upd = gather_shard(upd, ax, wire)[:flat_p.shape[0]]
    new_flat = flat_p + full_upd
    return _unflatten(new_flat, params), \
        ShardedAdamWState(step=step, mu=mu, nu=nu)
