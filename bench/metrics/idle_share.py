"""One minus the device's busy time (union of its operations) over the
traced part of the window."""


def read(rec):
    t = rec.get("trace") or {}
    if not t.get("window_s"):
        return None
    return 1.0 - t["busy_s"] / t["window_s"]
