"""device_idle_share: 100 x (1 - the union of the device's operation
intervals over the traced window), averaged over the chips."""


def read(run):
    t = run.trace
    if t is None or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s_mean() / t.window_s)
