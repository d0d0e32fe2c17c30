"""Tokens of every train step finished in the window, over the window (which
ends on the last step's ``block_until_ready``)."""


def read(rec):
    if "flops_per_token" not in rec:
        return None
    return rec["tokens"] / rec["window_s"]
