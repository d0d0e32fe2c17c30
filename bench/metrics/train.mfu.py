"""Train tokens per second times the operations a token needs (forward and
input gradient of every linear and of causal attention; no weight gradient
for frozen weights, no recomputation), over peak FLOP/s."""


def read(rec):
    if "flops_per_token" not in rec or "trace" not in rec:
        return None
    rate = rec["tokens"] / rec["window_s"]
    return rate * rec["flops_per_token"] / rec["peaks"][
        "bf16_flops_per_s"] * 100
