"""Median time to a posterior over every fit of the window."""

import numpy as np


def read(win):
    return float(np.percentile([f.latency for f in win.fits], 50))
