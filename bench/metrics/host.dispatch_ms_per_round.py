"""Host dispatch per round: self time of the program's ``put`` (the
packed buffers' upload), ``dispatch`` (the round program's launch),
``fetch`` (device results the host waits for: Oort's feedback) and
``eval`` spans, in ms per simulated round of the traced window. None
where the program has no ``put`` span."""
import programtrace
import tracefile

NAMES = ("put", "dispatch", "fetch", "eval")


def read(ctx):
    trace = programtrace.of(ctx)
    if trace is None or not ctx.rounds or not programtrace.has(trace, "put"):
        return None
    return tracefile.self_ns(programtrace.spans(trace, NAMES), NAMES) \
        / 1e6 / ctx.rounds
