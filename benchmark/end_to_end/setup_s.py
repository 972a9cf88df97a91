"""Process start to the start of the measured window: imports, the native
core's build, ``hvd.init()``, weights, compilation or the cache's read, the
check against the reference, traffic set-up, warm-up."""


def read(run):
    return run.window_start - run.process_start
