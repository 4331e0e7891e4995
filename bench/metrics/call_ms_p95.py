"""call_ms_p95: the 95th percentile of the calls' host spans in the
traced window (exact order statistic, nearest rank), in ms."""

import math


def read(run):
    t = run.trace
    if t is None or not t.calls:
        return None
    spans = sorted(b - a for a, b in t.calls)
    return 1e-6 * spans[max(0, math.ceil(0.95 * len(spans)) - 1)]
