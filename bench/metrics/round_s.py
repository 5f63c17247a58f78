"""Seconds per committed model block: the window over the rounds it ran."""


def read(rec):
    if rec["kind"] != "round" or not rec["round_walls"]:
        return None
    return rec["window_s"] / len(rec["round_walls"])
