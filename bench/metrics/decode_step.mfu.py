"""Least time the chip needs for the window's decode steps over their
measured time (the engine's ``engine/decode_step_s``), in percent.  A step's
least time is the larger of its model operations over peak FLOP/s and its
needed bytes (weights, the live rows' adapter factors, live keys and values)
over peak bandwidth; with a few dozen rows the bytes bind."""
from bench import flops


def bound(rec):
    m, pk = rec["sizes"], rec["peaks"]
    t_flops = t_bytes = 0.0
    for d in rec["decodes"]:
        t_flops += flops.decode_flops(m, d["contexts"]) / pk[
            "bf16_flops_per_s"]
        t_bytes += flops.decode_bytes(m, d["contexts"], d["adapters"], 2, 4,
                                      2) / pk["hbm_bytes_per_s"]
    return t_flops, t_bytes


def read(rec):
    steps = rec.get("tracker", {}).get("decode_step_s")
    if not steps or not rec.get("decodes"):
        return None
    n = min(len(steps), len(rec["decodes"]))
    rec = {**rec, "decodes": rec["decodes"][:n]}
    t_flops, t_bytes = bound(rec)
    return max(t_flops, t_bytes) / sum(steps[:n]) * 100
