"""Mean wall of the window's launches (``engine.launch_walls``: around
``DeviceProgram.run_batch``, ending in ``block_until_ready`` and the
read-back)."""
import numpy as np


def read(rec):
    w = [l["wall_s"] for l in rec["launches"]]
    return 1e3 * float(np.mean(w)) if w else None
