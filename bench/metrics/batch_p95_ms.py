"""The 95th percentile of batch latency over every batch of the window:
from the hand-off to the program's entry to the answers on the host (host
clock)."""
import numpy as np


def read(r):
    return float(np.percentile(r.window.latency, 95)) * 1e3
