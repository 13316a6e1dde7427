"""Read a JAX profiler trace (``*.xplane.pb``) into device ops and host spans.

Only JAX is needed: ``jax.profiler.ProfileData`` parses the file.  A TPU
device is a plane named ``/device:TPU:<n>``; its operations are the events
of its lines whose name holds ``XLA Ops``.  Host spans are the events of
every line of the ``/host:CPU`` plane, among them the benchmark's own
``jax.profiler.TraceAnnotation`` spans.  Device and host events share one
clock, in seconds here.

Also the interval arithmetic the per-layer metrics share: union, total
length and subtraction.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

__all__ = [
    "Event", "Trace", "load", "from_profile", "union", "total",
    "subtract", "matching", "is_collective", "COLLECTIVE",
]

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OP_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
#: HLO collectives, synchronous or split into start/done
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|collective-broadcast)(-start|-done)?$"
)
#: the opcode after the result type: the first word followed by "("
_OPCODE = re.compile(r"\s([a-z][a-z0-9-]*)\(")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float  # seconds
    end: float

    @property
    def duration(self) -> float:
        return self.end - self.start

    @property
    def op(self) -> str:
        """The HLO instruction's name.  A TPU op event is named by the
        instruction's whole text, ``%name = type opcode(operands), ...``,
        whose operands name other instructions."""
        head, sep, _ = self.name.partition(" = ")
        return head.lstrip("%") if sep else self.name

    @property
    def opcode(self) -> str:
        """The HLO opcode (``all-reduce``, ``custom-call``, ``fusion``);
        for an event named by its instruction alone, that name without its
        number.  JAX names a collective after its primitive (``psum.3``),
        so only the opcode tells collectives apart."""
        _, sep, text = self.name.partition(" = ")
        found = _OPCODE.search(text) if sep else None
        return found.group(1) if found else re.sub(r"\.\d+$", "", self.name)


@dataclasses.dataclass
class Trace:
    device_names: list[str]
    devices: list[list[Event]]  # per device: its operations, by start
    host: list[Event]  # every host event, by start

    def spans(self, name: str) -> list[Event]:
        return [e for e in self.host if e.name == name]


def _event(ev) -> Event:
    start = float(ev.start_ns) * 1e-9
    return Event(ev.name, start, start + float(ev.duration_ns) * 1e-9)


def from_profile(profile) -> Trace:
    """A ``Trace`` from a ``jax.profiler.ProfileData``."""
    names, devices, host = [], [], []
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = [
                _event(ev)
                for line in plane.lines if OP_LINE in line.name
                for ev in line.events
            ]
            names.append(plane.name)
            devices.append(sorted(ops, key=lambda e: e.start))
        elif plane.name == HOST_PLANE:
            host.extend(_event(ev) for line in plane.lines for ev in line.events)
    order = sorted(range(len(names)), key=lambda i: int(names[i].rsplit(":", 1)[1]))
    return Trace(
        [names[i] for i in order], [devices[i] for i in order],
        sorted(host, key=lambda e: e.start),
    )


def load(log_dir: str) -> Trace:
    """The newest ``*.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return from_profile(ProfileData.from_file(max(files, key=os.path.getmtime)))


def union(intervals) -> list[tuple[float, float]]:
    """Disjoint, sorted union of ``(start, end)`` intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals) -> float:
    return sum(e - s for s, e in union(intervals))


def subtract(a, b) -> list[tuple[float, float]]:
    """The parts of ``a`` that no interval of ``b`` covers."""
    out = []
    b = union(b)
    for s, e in union(a):
        cur = s
        for bs, be in b:
            if be <= cur or bs >= e:
                continue
            if bs > cur:
                out.append((cur, bs))
            cur = max(cur, be)
            if cur >= e:
                break
        if cur < e:
            out.append((cur, e))
    return out


def matching(events, text: str) -> list[Event]:
    """Events whose HLO instruction name holds ``text``.  A Pallas kernel
    is a custom call named after its jitted wrapper
    (``tiled_matmul_pallas.1``)."""
    return [e for e in events if text in e.op]


def is_collective(event: Event) -> bool:
    return bool(COLLECTIVE.match(event.opcode))
