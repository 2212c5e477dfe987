"""Readers over the traced window (``reduce/xplane.py``'s ``window``). Each
takes the reader context and its metric's ``args`` and returns a number, or
None when the trace holds nothing to read."""

from reduce import xplane


def idle_share_pct(r):
    """1 - union of the first device's operations over the traced window."""
    if r.win is None or not r.win["ops"]:
        return None
    return 100.0 * xplane.idle_share(r.win["ops"],
                                     r.win["hi"] - r.win["lo"])


def collective_ms_per_run(r, module, exposed):
    """Collective time per run of the program ``module`` on the first
    device, over its whole runs: all of it, or (``exposed``) the part during
    which no other operation ran."""
    if r.win is None:
        return None
    cut = xplane.whole_runs(r.win, module)
    if cut is None:
        return None
    runs, ops, asyncs = cut
    every, alone = xplane.collective_ns(ops, asyncs)
    return (alone if exposed else every) / len(runs) / 1e6


def op_time_share_pct(r, opcode, contains):
    """Device time of the operations with this opcode whose HLO text holds
    ``contains``, over the device's busy time."""
    if r.win is None or not r.win["ops"]:
        return None
    return (100.0 * xplane.kernel_ns(r.win["ops"], opcode, contains)
            / xplane.busy_ns(r.win["ops"]))


def flash_train_roofline_pct(r, module, opcode, contains):
    """The least time the chip could take for the flash calls of the whole
    runs of ``module`` (``flops.py``: the larger of operations over peak
    FLOP/s and bytes over peak bytes/s; at T 1024 and head size 64 the
    operations bound it), over the device time of those calls."""
    import flops
    if r.win is None or r.peak is None:
        return None
    cut = xplane.whole_runs(r.win, module)
    if cut is None:
        return None
    runs, ops, _ = cut
    spent = xplane.kernel_ns(ops, opcode, contains) / 1e9
    if spent <= 0:
        return None
    tokens = len(runs) * r.counters["tokens_per_step"] / r.chips
    seq_len = r.counters["seq_len"]
    least = max(
        tokens * flops.flash_train_flops_per_token(r.config, seq_len)
        / (r.peak["bf16_tflops"] * 1e12),
        tokens * flops.flash_train_bytes_per_token(r.config, seq_len)
        / (r.peak["hbm_gbps"] * 1e9))
    return 100.0 * least / spent


def train_mfu_pct(r, module):
    """Tokens per second of the device, from the start-to-start period of
    the whole runs of ``module``, times the operations a token requires
    (``flops.py``; recompute not counted, causal attention at half), over
    the chips' bf16 peak."""
    if r.win is None or r.peak is None:
        return None
    cut = xplane.whole_runs(r.win, module)
    if cut is None or len(cut[0]) < 2:
        return None
    runs = cut[0]
    period_s = (runs[-1][1] - runs[0][1]) / (len(runs) - 1) / 1e9
    per_token = r.family.train_flops_per_token(r.config,
                                               r.counters["seq_len"])
    return 100.0 * (r.counters["tokens_per_step"] / period_s * per_token
                    / (r.chips * r.peak["bf16_tflops"] * 1e12))


def module_mean_ms(r, module):
    """Mean device duration of the whole runs of the programs whose name
    holds ``module``."""
    if r.win is None:
        return None
    stats = xplane.module_stats(r.win["modules"], module)
    return None if stats is None else stats["mean_ns"] / 1e6
