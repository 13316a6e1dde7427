"""Front end (``core/api.py``): the engine's own host time per call, in
ms: each ``repro.matmul`` span of the window less the ``repro.dispatch``
spans inside it (plan lookup, padding checks, digest and executable
lookup, the crop of the result), mean over the window's calls.  Nothing
to read where the program opens no ``repro.matmul`` span."""
from __future__ import annotations


def read(run):
    lo, hi = run.window
    calls = [e for e in run.trace.spans("repro.matmul") if lo <= e.start and e.end <= hi]
    if not calls:
        return None
    dispatch = run.trace.spans("repro.dispatch")
    own = [
        c.duration - sum(d.duration for d in dispatch if c.start <= d.start and d.end <= c.end)
        for c in calls
    ]
    return 1e3 * sum(own) / len(own)
