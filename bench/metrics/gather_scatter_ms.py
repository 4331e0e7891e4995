"""gather_scatter_ms: device time per call, per chip, in operations that
gather, scatter or update a dynamic slice: their HLO opcode, or for a
fusion the opcodes it holds (``fusion[gather]``), as the trace's HLO
says.  Collectives (``all-gather``, ``reduce-scatter``) do not count."""

import re

PATTERN = re.compile(r"(?<![a-z-])(?<!all_)(?<!reduce_)"
                     r"(gather|scatter|dynamic-update-slice)")


def is_gather_scatter(name, category):
    return bool(PATTERN.search((category or name).lower()))


def read(run):
    t = run.trace
    if t is None or not t.calls:
        return None
    return 1e3 * t.op_seconds(is_gather_scatter) / len(t.calls)
