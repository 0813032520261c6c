"""Host scheduling per round: self time of the program's ``schedule`` and
``pack`` spans (``Simulator._begin_round``/``_schedule_round`` and
``RoundPipeline._preschedule``/``_materialize``), in ms per simulated
round of the traced window."""
import tracefile


def read(ctx):
    if not ctx.trace["spans"] or not ctx.rounds:
        return None
    return tracefile.self_ns(ctx.trace["spans"], ("schedule", "pack")) \
        / 1e6 / ctx.rounds
