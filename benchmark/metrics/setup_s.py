"""Set-up time in seconds: from the process's start to the window's
start (imports, the lake from the seed, index builds, the warm-up)."""


def read(w):
    return w.setup_s
