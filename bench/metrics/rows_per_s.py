"""Rows of the requests answered correctly, over the time from the
window's start to the last such reply (the requests due in the window,
drained)."""


def read(rec):
    done = [r["done"] for r in rec["requests"] if r["correct"]]
    if not done or max(done) <= 0:
        return None
    return len(done) * rec["rows_per_request"] / max(done)
