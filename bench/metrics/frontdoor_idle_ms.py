"""frontdoor_idle_ms: per call, the time chip 0 was idle while the
innermost program span on the calling thread was ``sort.run`` (the front
door: spec, validation, hand-off) or ``planner.choose`` (the plan lookup),
as the mean over the window's calls.

Program spans are the layer spans the program puts into the profiler
trace (``sort.run``, ``planner.*``, ``engine.*``, ``backend.*``,
``relational.*``, ...).  JAX's own host events nested inside one, such as
``PjitFunction(...)``, do not change which span the time goes to.  A trace
without ``sort.run`` or ``planner.choose`` gives nothing.

The device's timestamps are first put on the host's clock as far as
causality demands, call by call: a TPU trace can place a device program's
start before the host dispatched it (by 0.1 to 1.3 ms on a v5e, varying
between runs and drifting within one).  Each outermost ``PjitFunction(f)``
host event is paired, in order, with the runs of device module
``jit_f``; where a run of a call starts before its dispatch, the call's
device operations are moved later by the largest such lead.  Functions
whose dispatches and device runs do not pair one to one are left out of
the pairing, and operations of no paired call stay where they are (on the
CPU nothing pairs and nothing moves)."""

import bisect
import re

from benchkit.trace import Reduced

PROGRAM = re.compile(r"^(sort|planner|engine|backend|radix|select|"
                     r"relational|samplesort|distsort|spill)\.")
FRONT = ("sort.run", "planner.choose")
DISPATCH = "PjitFunction("


def innermost(host, names):
    """Sorted, disjoint intervals in which the innermost program span of
    ``host`` ([name, start, end] on one thread, so nested) is in
    ``names``."""
    spans = sorted(((n, a, b) for n, a, b in host if PROGRAM.match(n)),
                   key=lambda e: (e[1], -e[2]))
    out, stack, t = [], [], None

    def emit(upto):
        if stack and stack[-1][0] in names and upto > t:
            out.append((t, upto))

    for name, a, b in spans:
        while stack and stack[-1][1] <= a:
            end = stack[-1][1]
            emit(end)
            stack.pop()
            t = end
        emit(a)
        stack.append((name, min(b, stack[-1][1]) if stack else b))
        t = a
    while stack:
        end = stack[-1][1]
        emit(end)
        stack.pop()
        t = end
    return out


def overlap(xs, ys):
    """Total length shared by two sorted lists of disjoint intervals."""
    i = j = 0
    total = 0.0
    while i < len(xs) and j < len(ys):
        lo, hi = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if hi > lo:
            total += hi - lo
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return total


def on_host_clock(t):
    """Chip 0's operations of trace ``t``, each moved later by the lead of
    the call whose dispatch started its run (see the module doc)."""
    ops = sorted(t.ops.get(0, []), key=lambda o: o[2])
    dispatched, last_end = {}, float("-inf")
    for n, a, b in sorted(t.host, key=lambda e: (e[1], -e[2])):
        if n.startswith(DISPATCH) and a >= last_end:
            last_end = b
            module = "jit_" + n[len(DISPATCH):-1]
            dispatched.setdefault(module, []).append(a)
    runs, prev = {}, None
    for i, (name, _, _, _) in enumerate(ops):
        module = name.split("/")[0]
        if module != prev:
            runs.setdefault(module, []).append(i)
        prev = module
    paired = {}
    for module, starts in dispatched.items():
        if len(runs.get(module, ())) == len(starts):
            paired.update(zip(runs[module], starts))
    call_starts = [a for a, _ in t.calls]
    owner, lead, call = [], {}, None
    for i, (_, _, a, _) in enumerate(ops):
        if i in paired:
            h = paired[i]
            c = bisect.bisect_right(call_starts, h) - 1
            call = c if c >= 0 and h <= t.calls[c][1] else None
            if call is not None:
                lead[call] = max(lead.get(call, 0.0), h - a)
        owner.append(call)
    return [(n, cat, a + lead.get(c, 0.0), b + lead.get(c, 0.0))
            for (n, cat, a, b), c in zip(ops, owner)]


def read(run):
    t = run.trace
    if t is None or not t.calls or not any(n in FRONT for n, _, _ in t.host):
        return None
    t = Reduced(start=t.start, end=t.end, chips=t.chips, calls=t.calls,
                host=t.host, ops={0: on_host_clock(t)})
    return 1e-6 * overlap(innermost(t.host, FRONT), t.gaps(0)) / len(t.calls)
