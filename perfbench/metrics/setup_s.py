"""Set-up: process start to the first due request (weights built on the
device, programs compiled or loaded from the cache, warm-up)."""


def read(run):
    return run.setup_s
