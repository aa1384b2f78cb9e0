"""Padded slots over launched slots: the engine pads each launch up to
its bucket by replaying the last request."""


def read(rec):
    size = sum(l["size"] for l in rec["launches"])
    if not size:
        return None
    return sum(l["size"] - l["served"] for l in rec["launches"]) / size
