"""host_gap_ms: per call, the call's host span minus the time chip 0 was
busy inside it, as the mean over the window's calls: what the front door,
the planner and dispatch add to the device's work."""

from benchkit.trace import covered


def read(run):
    t = run.trace
    if t is None or not t.calls:
        return None
    busy = t.busy(0)
    gaps = [(b - a) - covered(busy, a, b) for a, b in t.calls]
    return 1e-6 * sum(gaps) / len(gaps)
