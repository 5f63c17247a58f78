"""The scheduler (``serve/scheduler.py``, ``slots.py``): the mean share of
slots decoding per tick."""


def read(rec):
    if rec["kind"] != "serve":
        return None
    return 100.0 * rec["occupancy"]
