"""Readers for the hybrid conv/attention configuration. A program without
the named kernels (an older commit) reads as None."""

import importlib

from reduce import xplane


def flash_roofline_pct(r, module, contains, flops):
    """The least time the chip could take for the flash calls of the whole
    runs of ``module`` (the functions ``flash_train_flops_per_token`` and
    ``flash_train_bytes_per_token`` of the benchmark's module ``flops``: the
    larger of operations over peak FLOP/s and bytes over peak bytes/s), over
    the device time of the custom calls whose instruction name holds one of
    ``contains``."""
    if r.win is None or r.peak is None:
        return None
    cut = xplane.whole_runs(r.win, module)
    if cut is None:
        return None
    runs, ops, _ = cut
    spent = sum(d for n, _, d in ops
                if xplane.hlo_opcode(n) == "custom-call"
                and any(c in xplane.instruction_name(n) for c in contains)
                ) / 1e9
    if spent <= 0:
        return None
    need = importlib.import_module(flops)
    tokens = len(runs) * r.counters["tokens_per_step"] / r.chips
    seq_len = r.counters["seq_len"]
    least = max(
        tokens * need.flash_train_flops_per_token(r.config, seq_len)
        / (r.peak["bf16_tflops"] * 1e12),
        tokens * need.flash_train_bytes_per_token(r.config, seq_len)
        / (r.peak["hbm_gbps"] * 1e9))
    return 100.0 * least / spent
