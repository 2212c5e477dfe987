"""What a model family's blocks save for their backward: one helper and
one policy for ``remat`` / ``remat_policy`` (documented at
``GPT2Config.remat_policy``), so that the families cannot drift apart."""

from __future__ import annotations

import flax.linen as nn
import jax

from horovod_tpu import tracing as _tracing
from horovod_tpu.ops.flash_attention import RESIDUAL_NAMES

_named = jax.checkpoint_policies.save_only_these_names(*RESIDUAL_NAMES)


def _flash_residuals(prim, *args, **params):
    """Save what the flash forward names of its own outputs, and count
    each yes (``tracing.note_residual_saved``)."""
    saved = _named(prim, *args, **params)
    if saved:
        _tracing.note_residual_saved()
    return saved


# ``dots``: keep what the MXU produced, recompute the cheap rest. That is
# the output of every product without batch dimensions, and the flash
# kernel's named outputs: it has no T x T score matrix to fear, and without
# them the backward runs the whole forward kernel again. A block with dense
# attention holds no such name, and the policy then saves what the plain one
# saves.
_DOTS = jax.checkpoint_policies.save_from_both_policies(
    jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
    _flash_residuals)


def remat_block(cls, cfg, static_argnums=()):
    """``cls`` as ``cfg.remat`` and ``cfg.remat_policy`` want it: as it is,
    under ``nn.remat`` with the ``dots`` policy, or under a plain
    ``nn.remat`` (``full``: nothing is saved)."""
    if not cfg.remat:
        return cls
    if cfg.remat_policy == "dots":
        return nn.remat(cls, static_argnums=static_argnums, policy=_DOTS)
    if cfg.remat_policy == "full":
        return nn.remat(cls, static_argnums=static_argnums)
    raise ValueError(f"unknown remat_policy {cfg.remat_policy!r}: "
                     "expected 'full' or 'dots'")
