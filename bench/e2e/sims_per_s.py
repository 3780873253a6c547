"""Simulations of every fit completed in the window, over its seconds."""


def read(win):
    return sum(f.waves for f in win.fits) * win.wave_size / win.seconds
