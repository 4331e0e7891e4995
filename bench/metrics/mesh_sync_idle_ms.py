"""mesh_sync_idle_ms: per call, the time chip 0 was idle while the
innermost program span on the calling thread was ``samplesort.sync``
(the mesh sample-sort reading its bucket counts on the host to size the
exchange), as the mean over the window's calls.

The device's operations are first put on the host's clock, and the
innermost span found, by the helpers of ``frontdoor_idle_ms`` (see its
doc).  A trace without ``samplesort.sync`` gives nothing."""

from benchkit import spec
from benchkit.trace import Reduced

SYNC = ("samplesort.sync",)


def read(run):
    t = run.trace
    if t is None or not t.calls or not any(n in SYNC for n, _, _ in t.host):
        return None
    front = spec.metric_module("frontdoor_idle_ms")
    t = Reduced(start=t.start, end=t.end, chips=t.chips, calls=t.calls,
                host=t.host, ops={0: front.on_host_clock(t)})
    idle = front.overlap(front.innermost(t.host, SYNC), t.gaps(0))
    return 1e-6 * idle / len(t.calls)
