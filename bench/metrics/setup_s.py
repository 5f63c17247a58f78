"""Process start to the opening of the window: data, weights, compilation
and warm-up."""


def read(rec):
    return rec["setup_s"]
