"""95th percentile of every step time in the window, in ms.  A step is
timed from the previous step's loss sync to its own (the trainer syncs
on every loss)."""

import numpy as np


def read(run):
    return float(np.percentile(np.asarray(run.step_s) * 1e3, 95))
