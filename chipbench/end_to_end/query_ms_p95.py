"""95th percentile latency, in milliseconds, of every query answered in
the window (open loop: from the time it was due), by linear interpolation
between order statistics."""

import numpy as np


def read(w):
    lat = [q.latency_s for q in w.answered]
    return 1e3 * float(np.percentile(lat, 95)) if lat else None
