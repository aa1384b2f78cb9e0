"""Mean time a request of the window waited in the engine's queue:
admission minus submission, on the engine's clock."""
import numpy as np


def read(rec):
    q = [r["admit"] - r["submit"] for r in rec["requests"]
         if r["admit"] is not None and r["submit"] is not None]
    return 1e3 * float(np.mean(q)) if q else None
