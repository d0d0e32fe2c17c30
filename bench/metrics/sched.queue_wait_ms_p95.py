"""95th percentile, over the window's requests, of the wall time from when a
request was due to the start of the engine step that admitted it."""
import numpy as np


def read(rec):
    if "tracker" not in rec or not rec.get("queue_wait_s"):
        return None
    return float(np.percentile(rec["queue_wait_s"], 95)) * 1e3
