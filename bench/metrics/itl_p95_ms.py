"""95th percentile of every gap between successive tokens of every request
due in the window."""
import numpy as np


def read(rec):
    if not rec.get("itl_s"):
        return None
    return float(np.percentile(rec["itl_s"], 95)) * 1e3
