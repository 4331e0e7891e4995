"""collective_ms: device time per call, per chip, in collective
operations (all-to-all, all-gather, all-reduce, reduce-scatter,
collective-permute, and their async start and done halves), by HLO
opcode as the trace's HLO says."""

import re

PATTERN = re.compile(r"(all|reduce|collective)[-_](to[-_]all|gather|reduce|"
                     r"scatter|permute)")


def is_collective(name, category):
    return bool(PATTERN.search((category or name).lower()))


def read(run):
    t = run.trace
    if t is None or not t.calls:
        return None
    return 1e3 * t.op_seconds(is_collective) / len(t.calls)
