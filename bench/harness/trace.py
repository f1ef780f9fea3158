"""Profiler trace of a short window, reduced to what the metrics read.

The runner records an XPlane trace with ``jax.profiler``; ``reduce`` reads it
with ``jax.profiler.ProfileData`` and keeps, for every device plane, the
events of its op line (``XLA Ops``) and of its program line (``XLA Modules``),
each with the text of its string stats, which for an op holds the HLO
instruction with its operand shapes. Busy time is the union of a chip's op
intervals; the idle share is one minus busy time over the traced window.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SHAPE = re.compile(r"\b(s8|u8|s16|s32|u32|bf16|f16|f32|pred)\[([0-9,]*)\]")
# An op event is named by its HLO instruction, ``%name = type opcode(operands)``.
# Control flow (a scan's while loop) spans the ops of its body: it is left out
# of busy time and of the op breakdown, which count the body's ops themselves.
CONTAINERS = ("%while", "%conditional", "%call")
INSTR = re.compile(r"^(%[\w.\-]+) = (\w+\[[0-9,]*\])\S* ([\w\-]+)\(")
# host events that say nothing about why the device waits: the client's event
# loop, thread plumbing and the profiler's own calls
HOST_NOISE = ("Profile", "profiler.py", "base_events.py", "selectors.py", "events.py",
              "threading.py", "queues.py")


@dataclasses.dataclass
class Event:
    name: str
    start_ns: float
    dur_ns: float
    text: str          # the event's string stats, joined

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Chip:
    name: str
    ops: List[Event]
    modules: List[Event]


@dataclasses.dataclass
class Trace:
    chips: List[Chip]
    host: Dict[str, List[Event]]     # "<plane>/<line index>/<thread>" -> events
    window_s: float                  # the traced window, host clock

    @property
    def busy_s(self) -> float:
        """Seconds in which some operation ran, averaged over the chips."""
        if not self.chips:
            return 0.0
        return sum(union_ns(self.leaf_ops(c) or c.modules)
                   for c in self.chips) / len(self.chips) / 1e9

    def ops(self) -> List[Event]:
        return [e for c in self.chips for e in c.ops]

    def leaf_ops(self, chip: "Chip") -> List[Event]:
        return [e for e in chip.ops if not e.name.startswith(CONTAINERS)]


def union_ns(events: List[Event]) -> float:
    total, end = 0.0, None
    for e in sorted(events, key=lambda e: e.start_ns):
        if end is None or e.start_ns > end:
            total += e.dur_ns
            end = e.end_ns
        elif e.end_ns > end:
            total += e.end_ns - end
            end = e.end_ns
    return total


def idle_gaps(events: List[Event]) -> List[tuple]:
    """(start_ns, end_ns) of the gaps between busy intervals, longest first."""
    out, end = [], None
    for e in sorted(events, key=lambda e: e.start_ns):
        if end is not None and e.start_ns > end:
            out.append((end, e.start_ns))
        end = e.end_ns if end is None else max(end, e.end_ns)
    return sorted(out, key=lambda g: g[0] - g[1])


def shapes(text: str) -> List[tuple]:
    """(dtype, dims) of every array type in an HLO instruction's text, the
    result first, then the operands in order."""
    return [(m.group(1), tuple(int(x) for x in m.group(2).split(",") if x))
            for m in SHAPE.finditer(text)]


def short_name(name: str) -> str:
    """``%copy.135 s8[1,8192,8,8,64] copy`` for an HLO instruction's full text."""
    m = INSTR.match(name)
    return " ".join(m.groups()) if m else name[:120]


def _text(ev) -> str:
    parts = []
    for k, v in ev.stats:
        if isinstance(v, str):
            parts.append(f"{k}={v}")
    return " ".join(parts)


def _events(line) -> List[Event]:
    return [Event(e.name, float(e.start_ns), float(e.duration_ns), _text(e))
            for e in line.events]


def reduce(log_dir: str, window_s: float) -> Trace:
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return from_profile(ProfileData.from_file(max(files, key=os.path.getmtime)),
                        window_s)


def from_profile(pd, window_s: float) -> Trace:
    chips, host = [], {}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and "SparseCore" not in plane.name:
            lines = {ln.name: ln for ln in plane.lines}
            ops = _events(lines[OPS_LINE]) if OPS_LINE in lines else []
            mods = _events(lines[MODULES_LINE]) if MODULES_LINE in lines else []
            if ops or mods:
                chips.append(Chip(plane.name, ops, mods))
        elif plane.name.startswith("/host:"):
            for i, ln in enumerate(plane.lines):       # thread names repeat
                evs = _events(ln)
                if evs:
                    host[f"{plane.name}/{i}/{ln.name}"] = evs
    return Trace(chips=chips, host=host, window_s=window_s)


def host_at(trace: Trace, start_ns: float, end_ns: float) -> Optional[str]:
    """What the host was doing over a gap: the innermost host event (the
    shortest) that covers at least half of it, else the one that covers most;
    events of the client's event loop and of the profiler are skipped. None
    where no event overlaps the gap."""
    half = (end_ns - start_ns) / 2.0
    inner = best = None
    cover_best = 0.0
    for evs in trace.host.values():
        for e in evs:
            if any(n in e.name for n in HOST_NOISE):
                continue
            c = min(e.end_ns, end_ns) - max(e.start_ns, start_ns)
            if c >= half and (inner is None or e.dur_ns < inner.dur_ns):
                inner = e
            if c > cover_best:
                best, cover_best = e, c
    pick = inner or best
    return pick.name if pick is not None else None


def breakdown(trace: Trace, top: int = 10) -> dict:
    """The device operations that took most time (summed by name over the
    chips) and the longest idle gaps of the first chip, named by what the
    host was doing in them."""
    by_name: Dict[str, float] = {}
    for c in trace.chips:
        for e in trace.leaf_ops(c):
            k = short_name(e.name)
            by_name[k] = by_name.get(k, 0.0) + e.dur_ns / 1e9
    ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    gaps = []
    if trace.chips:
        c = trace.chips[0]
        for s, e in idle_gaps(trace.leaf_ops(c) or c.modules)[:top]:
            gaps.append([host_at(trace, s, e) or "unattributed", (e - s) / 1e9])
    return {"device_ops": [[k, v] for k, v in ops], "idle_gaps": gaps}
