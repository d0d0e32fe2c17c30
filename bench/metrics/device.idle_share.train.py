"""Share of the traced window in which no operation ran on the device."""


def read(rec):
    t = rec.get("trace")
    if t is None:
        return None
    return (1 - t["busy_s"] / t["window_s"]) * 100
