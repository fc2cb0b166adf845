"""Set-up: from the process's start to the first due request. Loading,
making the weights, compiling (from the cache after a cell's first run)
and warming up both steps."""


def read(run):
    return run.setup_s
