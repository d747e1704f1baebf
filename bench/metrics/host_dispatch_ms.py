"""Mean host time of one training-step dispatch: the program's own
`fleet.dispatch` spans (repro.obs) that lie inside the benchmark's
`bench.window` span, both on `time.perf_counter`."""


def read(ctx):
    try:
        from repro import obs
    except ImportError:
        return None
    window = ctx["spans"].spans.get("bench.window")
    if not window:
        return None
    lo, hi = window[0]
    secs = [e - s for name, s, e in obs.spans()
            if name == "fleet.dispatch" and lo <= s and e <= hi]
    if not secs:
        return None
    return 1e3 * sum(secs) / len(secs)
