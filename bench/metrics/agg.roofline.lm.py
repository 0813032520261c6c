"""The aggregation's share of its roofline in the lm cells, in %: for every
aggregating round of the traced window, the least time Eq. 2 over its ``n``
arrived rows of width ``D`` could take on the chip (the larger of
``saa_min_bytes(n, D)`` over the HBM bandwidth and ``saa_flop(n, D)`` over
the bf16 peak, ``counts/work.py``), summed, over the own device time of the
ops under the round program's ``fresh_sum``, ``cache``, ``aggregate`` and
``apply`` named scopes, averaged over the chips. The count is of the
algorithm's work, whatever implements it: the fresh rows summed in the
training loop or read from a stack. None where no op carries those
scopes."""
import programtrace
import tracefile
from counts import work

SCOPES = ("fresh_sum", "cache", "aggregate", "apply")


def read(ctx):
    trace = programtrace.of(ctx)
    if trace is None or not ctx.agg_rows or ctx.peak is None:
        return None
    planes = [evs for evs in trace["device"].values() if evs]
    own = [tracefile.op_self_ns([[scope, s, d] for _n, s, d, scope in evs])
           for evs in planes]
    ns = sum(t for per in own for scope, t in per.items()
             if any(programtrace.in_scope(scope, n) for n in SCOPES))
    if not ns:
        return None
    t_min = sum(max(work.saa_min_bytes(n, ctx.d) / ctx.peak["hbm_bytes_per_s"],
                    work.saa_flop(n, ctx.d) / ctx.peak["bf16_flops"])
                for n in ctx.agg_rows)
    return 100.0 * t_min / (ns / 1e9 / len(planes))
