"""95th percentile of time to first token over every request due in the
window, from when it was due to when its first token reached the host; a
request with no first token counts the whole wait until the run stopped."""
import numpy as np


def read(rec):
    if "ttft_s" not in rec:
        return None
    return float(np.percentile(rec["ttft_s"], 95)) * 1e3
