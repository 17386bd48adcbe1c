"""95th percentile of the latency of every request completed in the
window, in milliseconds."""
import numpy as np


def read(rec):
    lat = rec["latencies_s"]
    return float(np.percentile(lat, 95)) * 1e3 if lat else None
