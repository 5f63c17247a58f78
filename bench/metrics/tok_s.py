"""Output tokens delivered in the window over its length: the work the
server completed while requests kept arriving."""


def read(rec):
    if rec["kind"] != "serve":
        return None
    seconds = rec["seconds"]
    return sum(n for t, n in rec["deliveries"] if t <= seconds) / seconds
