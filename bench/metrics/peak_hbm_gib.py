"""peak_hbm_gib: the device allocator's ``peak_bytes_in_use`` after the
window, in GiB; on several chips the largest of them.  None where the
device does not report it."""


def read(run):
    if run.peak_bytes is None:
        return None
    return run.peak_bytes / 2 ** 30
