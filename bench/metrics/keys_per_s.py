"""keys_per_s: input keys of every call in the window over the window's
whole time, on the host clock (from the window's opening to the end of
the call in flight at its deadline)."""


def read(run):
    return run.keys_per_call * len(run.calls) / run.window_s
