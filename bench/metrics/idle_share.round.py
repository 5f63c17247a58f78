"""The device's idle share of the traced window (rounds)."""


def read(rec):
    tr = rec.get("trace")
    if rec["kind"] != "round" or not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
