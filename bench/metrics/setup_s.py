"""Set-up: process start to the start of the window (import, weights,
compiles or cache loads, warm-up, the check's first iterations or the
stream's fill)."""


def read(w):
    return w["setup_s"]
