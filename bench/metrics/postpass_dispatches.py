"""postpass_dispatches: per call, the JAX dispatches the calling thread
made inside the relational post-pass (``relational.post_pass`` spans),
as the mean over the window's calls: how many device programs the
post-pass is split into.

A dispatch is an outermost ``PjitFunction(...)`` host event: JAX records
each dispatch as such an event holding a nested one of the same name, and
dispatches nested in another are part of it.  A trace without
``relational.post_pass`` gives nothing."""

import bisect

from benchkit.trace import union

POST_PASS = "relational.post_pass"
DISPATCH = "PjitFunction("


def read(run):
    t = run.trace
    if t is None or not t.calls:
        return None
    posts = union([(a, b) for n, a, b in t.host if n == POST_PASS])
    if not posts:
        return None
    starts = [a for a, _ in posts]
    count, last_end = 0, float("-inf")
    for n, a, b in sorted(t.host, key=lambda e: (e[1], -e[2])):
        if not n.startswith(DISPATCH) or a < last_end:
            continue
        last_end = b
        i = bisect.bisect_right(starts, a) - 1
        count += i >= 0 and a <= posts[i][1]
    return count / len(t.calls)
