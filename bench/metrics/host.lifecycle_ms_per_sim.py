"""Host lifecycle per simulation: self time of the program's ``build``
(``Simulator`` construction), ``upload`` (``RoundPipeline`` construction
and its uploads) and ``finalize`` spans, in ms per simulation of the
traced window (one ``finalize`` each). None where the program lacks
these spans."""
import programtrace
import tracefile

NAMES = ("build", "upload", "finalize")


def read(ctx):
    trace = programtrace.of(ctx)
    if trace is None or not programtrace.has(trace, *NAMES):
        return None
    life = programtrace.spans(trace, NAMES)
    sims = sum(1 for s in life if s[0] == "finalize")
    return tracefile.self_ns(life, NAMES) / 1e6 / sims
