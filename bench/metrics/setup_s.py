"""Process start to the window's start: weights, engine or train state,
compiles (or compile-cache loads) and warm-up."""


def read(rec):
    return rec["setup_s"]
