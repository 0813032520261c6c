"""Reductions that several per-layer metrics share; each metric's reader
in ``metrics/`` names the one it reports."""
import tracefile


def idle_share(ctx):
    """Share of the traced window in which no operation ran on the device,
    in %, averaged over the chips: 100 * (1 - busy / window)."""
    busy = tracefile.busy_s(ctx.trace)
    if busy is None:
        return None
    return 100.0 * (1.0 - busy / ctx.window_s)


def train_mfu(ctx):
    """The whole round step's share of the chip's bf16 peak, in %: training
    FLOP of the learner rows trained in the traced window (counted from the
    configuration's shapes, ``counts/work.py``) over window seconds, chips
    and the peak of the ``device_kind``."""
    if not ctx.train_flop or ctx.peak is None:
        return None
    return 100.0 * ctx.train_flop / (ctx.window_s * ctx.chips
                                     * ctx.peak["bf16_flops"])
