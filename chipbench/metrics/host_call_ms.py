"""Front end (``core/api.py``): host time of ``DistributedMatmul.__call__``
from entry to return, before the wait on its result; mean over the
window's calls, in ms.  The benchmark's own span around the call."""
from __future__ import annotations


def read(run):
    if not run.host_call_s:
        return None
    return 1e3 * sum(run.host_call_s) / len(run.host_call_s)
