"""Median of the engine's ``engine/decode_step_s`` spans (decode, sampling
and the tokens' copy to the host) in the traced run's window."""
import numpy as np


def read(rec):
    vals = rec.get("tracker", {}).get("decode_step_s")
    return float(np.median(vals)) * 1e3 if vals else None
