"""Process start to the first timed fit."""


def read(win):
    return win.setup_s
