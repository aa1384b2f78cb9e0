"""Device time of the resident loop's module per traced launch."""
import numpy as np


def read(rec):
    loops = (rec.get("trace") or {}).get("loop_s") or []
    return 1e3 * float(np.mean(loops)) if loops else None
