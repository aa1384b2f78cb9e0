"""What a configuration module hands the harness.

A configuration is ``configs/<name>.json`` (its sizes, source, cuts and
guarantees) beside ``configs/<name>.py``, whose ``load(cfg, seed)``
generates the data from the seed and returns a :class:`Workload`.  The
plain reference and the control live in that module too; neither imports
anything of the program under test.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable

import numpy as np

# request indices at and above this serve the warm-up, below it the window
WARM_BASE = 1 << 31


@dataclass
class Workload:
    program: object                 # the @revet.program under test
    statics: dict                   # compile-time constants of the program
    output: str                     # DRAM array that holds the answers
    rows_per_request: int
    # (index, rows) -> (arrays, scalars) of one request; the same index
    # gives the same request, and every request has the same shapes
    request: Callable[[int, int], tuple[dict, dict]]
    # index -> the answers of the plain reference, one per row
    reference: Callable[[int], np.ndarray]
    # index -> the answers of the control: the reference with one of the
    # configuration's guarantees broken (must fail the comparison)
    control: Callable[[int], np.ndarray]
