"""DRAM image of a launch: the compiled DRAM arrays' words times the
launch's slots times 4 bytes, averaged over the window's launches."""
import numpy as np


def read(rec):
    sizes = [l["size"] for l in rec["launches"]]
    if not sizes:
        return None
    return float(np.mean(sizes)) * rec["image_bytes_per_slot"] / 2 ** 20
