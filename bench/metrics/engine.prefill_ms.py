"""Median of the engine's ``engine/prefill_s`` spans (one prefill call:
dispatch to the call's return) in the traced run's window."""
import numpy as np


def read(rec):
    vals = rec.get("tracker", {}).get("prefill_s")
    return float(np.median(vals)) * 1e3 if vals else None
