"""Nonuniform layout (``core/api.py`` ``NonuniformMatmul``,
``core/blocking.py`` ``bucketize``): the share, in %, of the padded
product's FLOPs that the answer needs, ``100 prod(1 - padding_waste)``
over rows, inner and columns: the logical N^3 over the padded extents the
engine planned.  From the program's counter ``padding_waste``.  Nothing
to read where the entry keeps no such counter."""
from __future__ import annotations

import math


def read(run):
    waste = run.counters.get("padding_waste")
    if not waste:
        return None
    return 100.0 * math.prod(1.0 - w for w in waste.values())
