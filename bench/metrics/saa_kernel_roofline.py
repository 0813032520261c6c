"""The SAA Pallas kernel's share of its roofline, in %: for every round of
the traced window, the least time the chip could take for its aggregation
(the larger of the minimal bytes over HBM bandwidth and the FLOP over the
bf16 peak, ``counts/work.py``), summed, over the kernel's device time in
the trace. None where the trace holds no event of the kernel."""
import tracefile
from counts import work

KERNEL_NAMES = ("sweep_fused_staleness_apply",)


def read(ctx):
    evs = tracefile.kernel_events(ctx.trace, KERNEL_NAMES)
    if not evs or not ctx.agg_rows or ctx.peak is None:
        return None
    t_min = sum(max(work.saa_min_bytes(n, ctx.d) / ctx.peak["hbm_bytes_per_s"],
                    work.saa_flop(n, ctx.d) / ctx.peak["bf16_flops"])
                for n in ctx.agg_rows)
    t_kernel = sum(e[2] for e in evs) / 1e9 / ctx.chips
    return 100.0 * t_min / t_kernel
