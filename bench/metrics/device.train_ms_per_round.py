"""Device time of local training per round: the own time (less nested
ops) of the device ops under the round program's ``train`` named scope,
summed, averaged over the chips, in ms per simulated round of the traced
window. None where no op carries that scope."""
import programtrace
import tracefile


def read(ctx):
    trace = programtrace.of(ctx)
    if trace is None or not ctx.rounds:
        return None
    planes = [evs for evs in trace["device"].values() if evs]
    # own time per scope path (op names repeat across programs; paths
    # serve as keys just as well)
    own = [tracefile.op_self_ns([[scope, s, d] for _n, s, d, scope in evs])
           for evs in planes]
    train = [ns for per in own for scope, ns in per.items()
             if programtrace.in_scope(scope, "train")]
    if not train:
        return None
    return sum(train) / len(planes) / 1e6 / ctx.rounds
