"""Paged decode attention: least time for the traced decode steps' attention
(live keys and values over peak bandwidth, or its operations over peak
FLOP/s, whichever is larger) over the kernel's device time in the trace."""
from bench import trace
from bench.kernels import paged_decode_attention as k


def read(rec):
    steps = rec.get("trace_decodes")
    if "trace" not in rec or not steps:
        return None
    secs = trace.module_op_time(rec["trace"], "_decode", "paged_decode")
    if secs <= 0:
        return None
    m, pk = rec["sizes"], rec["peaks"]
    lengths = [c for d in steps for c in d["contexts"]] * m["layers"]
    t = max(k.flops(lengths, m["heads"], m["head_dim"])
            / pk["bf16_flops_per_s"],
            k.bytes_moved(lengths, m["heads"], m["kv_heads"], m["head_dim"],
                          2) / pk["hbm_bytes_per_s"])
    return t / secs * 100
