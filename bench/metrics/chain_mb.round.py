"""The chain (``core/blockchain.py``): megabytes of payload appended per
round, model block and update blocks."""


def read(rec):
    if rec["kind"] != "round" or not rec["round_walls"]:
        return None
    return rec["chain_bytes"] / len(rec["round_walls"]) / 1e6
