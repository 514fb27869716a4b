"""Set-up: process start to the window's opening (imports, data, weights,
the warm-up round and every compile or cache load)."""


def read(run):
    return run.setup_s
