"""Set-up time: from the process's start to the window's, on the host
clock: imports, finding the chip, deploying and compiling (or loading
from the compile cache), filling the records and warming every shape."""


def read(run):
    return run.setup_s
