"""setup_s: seconds from process start until the window opens: JAX
start-up, the inputs made on the device, warm-up with compilation or its
load from the persistent cache.  Host clock."""


def read(run):
    return run.setup_s
