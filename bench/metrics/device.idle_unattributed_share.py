"""Share of the device's idle time that no program span explains, in %:
over the window from the first program span to the last, the idle time
of the first device (no op running) during which no span of the program
(any name it has) is open on the host, over all its idle time. None
where the program lacks the lifecycle spans (``build``), so that the
time between rounds has no span to fall in."""
import programtrace
import tracefile


def _clipped(events, win):
    return [[None, max(s, win[0]), min(s + d, win[1]) - max(s, win[0])]
            for _n, s, d, *_ in events if s < win[1] and s + d > win[0]]


def read(ctx):
    trace = programtrace.of(ctx)
    if trace is None or not programtrace.has(trace, "build"):
        return None
    planes = [evs for evs in trace["device"].values() if evs]
    win = programtrace.window(trace)
    if not planes or win is None:
        return None
    busy = _clipped(planes[0], win)
    idle = (win[1] - win[0]) - tracefile.busy_ns(busy)
    if not idle:
        return None
    unexplained = (win[1] - win[0]) - tracefile.busy_ns(
        busy + _clipped(trace["spans"], win))
    return 100.0 * unexplained / idle
