"""Readers for the block-diffusion configuration. A program without the
named kernels (an older commit) reads as None."""

from reduce import xplane


def bd_flash_roofline_pct(r, module, contains):
    """The least time the chip could take for the flash calls of the whole
    runs of ``module`` under the block-diffusion mask (``flops_sdar.py``:
    the larger of operations over visible pairs over peak FLOP/s and
    grouped-query bytes over peak bytes/s; at head size 128 and 8,192
    positions the operations bound it), over the device time of the custom
    calls whose instruction name holds one of ``contains``."""
    import flops_sdar
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
    tokens = len(runs) * r.counters["tokens_per_step"] / r.chips
    seq_len = r.counters["seq_len"]
    least = max(
        tokens * flops_sdar.flash_train_flops_per_token(r.config, seq_len)
        / (r.peak["bf16_tflops"] * 1e12),
        tokens * flops_sdar.flash_train_bytes_per_token(r.config, seq_len)
        / (r.peak["hbm_gbps"] * 1e9))
    return 100.0 * least / spent
