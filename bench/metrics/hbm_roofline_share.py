"""hbm_roofline_share: the least time the chips' HBM needs to read the
calls' inputs once and write their outputs once (the bytes at the API,
counted from shapes by the operation) over the device busy time of the
window, in %.  The bandwidth comes from ``bench/peaks.json`` only."""


def read(run):
    t = run.trace
    if t is None or run.peaks is None:
        return None
    busy = t.busy_s_mean()
    if busy <= 0:
        return None
    calls = sum(1 for a, b in t.calls if t.start <= a and b <= t.end)
    least = (run.api_bytes_per_call * calls
             / (run.peaks["hbm_bytes_per_s"] * run.chips))
    return 100.0 * least / busy
