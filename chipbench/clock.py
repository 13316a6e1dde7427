"""Each chip's clock against the host's, from the window's trace.

The profiler stamps host events on the host's clock and each chip's
operations on that chip's own; in the traces of this benchmark the two
disagree by one to two milliseconds, as much as the idle gap between two
products.  Two causal rules bound the offset ``delta`` (host time = device
time + ``delta``) in every product of the window:

1. a chip cannot start a product before the host launched it:
   ``delta >= launch - first op's start``;
2. the host cannot learn that a product is done before its last op ends:
   ``delta <= done - last op's end``.

The tightest bracket over the window's products is returned, with its
midpoint.  The products are the engine's ``repro.matmul`` spans in the
window, or, for a program without them, the benchmark's ``chipbench.call``
spans; a chip's operations are split into as many products, by count
where they divide evenly, else at their widest idle gaps (the loop is
closed: one product at a time).  A whole
call, not its ``repro.execute``: a nonuniform call enqueues its padding
gathers before the product, and they open its run on the chip.

Launch, tightest first: the runtime's ``DoEnqueueProgram`` (earliest over
its threads, as it is not known which thread served which chip), its
``PJRT_LoadedExecutable_Execute``, else the engine's ``repro.dispatch``.
Done: the runtime's ``tpu::System::Execute=>Done`` (latest over its
threads), else the end of the benchmark's ``chipbench.wait``.
"""
from __future__ import annotations

import dataclasses

from chipbench import xplane

#: host events that start before a product can start on a chip, tightest first
LAUNCH = ("DoEnqueueProgram", "PJRT_LoadedExecutable_Execute", "repro.dispatch")
#: host events that start (or, for chipbench.wait, end) after a product ended
DONE = ("tpu::System::Execute=>Done", "chipbench.wait")


@dataclasses.dataclass(frozen=True)
class Offset:
    """``delta`` of one chip lies in ``[lo, hi]`` seconds."""

    lo: float
    hi: float
    launch: str  # the LAUNCH event that set ``lo``
    done: str  # the DONE event that set ``hi``

    @property
    def mid(self) -> float:
        return 0.5 * (self.lo + self.hi)

    def __str__(self) -> str:
        return (
            f"[{1e3 * self.lo:.3f}, {1e3 * self.hi:.3f}] ms"
            f" ({self.launch} / {self.done})"
        )


def _in_window(spans, window):
    lo, hi = window
    return [e for e in spans if lo <= e.start and e.end <= hi]


def products(ops, n: int):
    """A chip's operations split into ``n`` products: ``[(first start, last
    end)]``.  Every call of the closed loop runs the same programs, so
    where the chip ran a multiple of ``n`` operations, each product is the
    next ``len(ops) // n`` of them by start: a host stall inside a call
    can leave an idle gap wider than those between calls.  Else the busy
    intervals are split at the ``n - 1`` widest idle gaps; None when the
    chip has fewer than ``n`` of them."""
    if n >= 1 and ops and len(ops) % n == 0:
        ordered = sorted(ops, key=lambda e: e.start)
        per = len(ops) // n
        groups = [ordered[i * per:(i + 1) * per] for i in range(n)]
        return [(min(e.start for e in g), max(e.end for e in g)) for g in groups]
    busy = xplane.union([(e.start, e.end) for e in ops])
    if n < 1 or len(busy) < n:
        return None
    gaps = sorted(range(1, len(busy)), key=lambda i: busy[i][0] - busy[i - 1][1])
    cuts = sorted(gaps[len(gaps) - (n - 1):]) if n > 1 else []
    edges = [0] + cuts + [len(busy)]
    return [(busy[a][0], busy[b - 1][1]) for a, b in zip(edges, edges[1:])]


def _host_products(trace, window):
    """Per product of the window: its host interval, from its anchor's start
    to the next anchor's start (the window's end for the last)."""
    anchors = _in_window(trace.spans("repro.matmul"), window)
    if not anchors:
        anchors = _in_window(trace.spans("chipbench.call"), window)
    starts = [e.start for e in anchors]
    return list(zip(starts, starts[1:] + [window[1]]))


def _bound(trace, names, span, pick, at_end=False):
    """``pick`` (min or max) of the first of ``names`` with events starting
    in ``span``: ``(time, name)``, or None."""
    s, e = span
    for name in names:
        times = [
            ev.end if at_end else ev.start
            for ev in trace.spans(name) if s <= ev.start < e
        ]
        if times:
            return pick(times), name
    return None


def offsets(run):
    """Per chip of the cell, the ``Offset`` over the window's products;
    None when a bound is missing or the bracket is empty on any chip."""
    hosts = _host_products(run.trace, run.window)
    if not hosts:
        return None
    bounds = []
    for span in hosts:
        launch = _bound(run.trace, LAUNCH, span, min)
        done = _bound(run.trace, DONE[:1], span, max) or _bound(
            run.trace, DONE[1:], span, max, at_end=True
        )
        if launch is None or done is None:
            return None
        bounds.append((launch, done))
    out = []
    for ops in run.device_ops():
        dev = products(ops, len(hosts))
        if dev is None:
            return None
        lows = [(t - first, name) for ((t, name), _), (first, _) in zip(bounds, dev)]
        highs = [(t - last, name) for (_, (t, name)), (_, last) in zip(bounds, dev)]
        (lo, launch), (hi, done) = max(lows), min(highs)
        if lo > hi:
            return None
        out.append(Offset(lo, hi, launch, done))
    return out or None
