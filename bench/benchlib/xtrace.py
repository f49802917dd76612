"""Reduction of a JAX profiler trace (`*.xplane.pb`) to device numbers.

A TPU trace has one plane per chip, "/device:TPU:<i>", with a line "XLA Ops"
(one event per HLO operation executed) and a line "XLA Modules" (one event
per program execution). Host threads are lines of the plane "/host:CPU".
Every event has a start and a duration in nanoseconds on one clock.

An op event's name is its HLO text, "%<instruction> = <shape> <op>(...)";
the reduction keys ops by the instruction name before " = ".

* busy: the union of a chip's op intervals;
* kernel time: the summed durations of the ops whose instruction name
  starts with the kernel's name (a Pallas call's `name=`, e.g.
  "pc2im_fps_tile.2"); ops that only take a kernel's output do not count;
* gaps between programs: from the end of one module execution to the start
  of the next on the same chip;
* idle gaps: holes in the union of op intervals, each named by the host
  event that overlaps it most (events longer than a second, such as
  whole-window annotations, name nothing).
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_SPAN_MAX_NS = 1e9


HLO_HEAD = re.compile(r"^(\S+?)(?:\{[^}]*\})?\s+([\w-]+)\(")


@dataclasses.dataclass(frozen=True)
class Event:
    """One trace event: name, start and duration (ns); `label` for display."""

    name: str
    start: float
    dur: float
    label: str = ""

    @property
    def end(self) -> float:
        """End of the event in ns."""
        return self.start + self.dur


@dataclasses.dataclass
class Trace:
    """Device ops and modules per chip, and every host event."""

    ops: dict[int, list[Event]]
    modules: dict[int, list[Event]]
    host: list[Event]

    @property
    def chips(self) -> list[int]:
        """Chip ordinals that ran at least one op."""
        return sorted(d for d, evs in self.ops.items() if evs)


def find_xplane(log_dir: str) -> str:
    """The newest `*.xplane.pb` a profiler session wrote under `log_dir`."""
    found = glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(found, key=os.path.getmtime)


def _op_event(e) -> Event:
    """An "XLA Ops" event keyed by its instruction name.

    Its label adds the output shape and op kind where the HLO text shows them.
    """
    name, _, rest = e.name.partition(" = ")
    name = name.lstrip("%")
    m = HLO_HEAD.match(rest)
    label = f"{name}: {m.group(1)} {m.group(2)}" if m else name
    return Event(name, e.start_ns, e.duration_ns, label)


def load(path: str) -> Trace:
    """Read an xplane file into a `Trace` (needs only `jax.profiler`)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops: dict[int, list[Event]] = {}
    modules: dict[int, list[Event]] = {}
    host: list[Event] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.setdefault(dev, []).extend(_op_event(e) for e in line.events)
                elif line.name == MODULES_LINE:
                    modules.setdefault(dev, []).extend(
                        Event(e.name, e.start_ns, e.duration_ns) for e in line.events
                    )
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend(Event(e.name, e.start_ns, e.duration_ns) for e in line.events)
    for d in ops:
        ops[d].sort(key=lambda e: e.start)
    for d in modules:
        modules[d].sort(key=lambda e: e.start)
    host.sort(key=lambda e: e.start)
    return Trace(ops, modules, host)


def union(events: list[Event]) -> list[tuple[float, float]]:
    """Merged [start, end) intervals covered by `events` (sorted by start)."""
    out: list[tuple[float, float]] = []
    for e in sorted(events, key=lambda e: e.start):
        if out and e.start <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e.end))
        else:
            out.append((e.start, e.end))
    return out


def busy_s(trace: Trace) -> float:
    """Seconds in which some op ran, averaged over the chips that ran ops."""
    chips = trace.chips
    if not chips:
        return 0.0
    total = sum(b - a for d in chips for a, b in union(trace.ops[d]))
    return total / len(chips) * 1e-9


def kernel_events(trace: Trace, kernel: str) -> list[Event]:
    """Ops on any chip whose instruction name starts with `kernel`."""
    return [e for d in trace.chips for e in trace.ops[d] if e.name.startswith(kernel)]


def module_gaps_s(trace: Trace) -> list[float]:
    """Seconds from each program's end to the next program's start, per chip."""
    gaps = []
    for evs in trace.modules.values():
        for a, b in zip(evs, evs[1:]):
            gaps.append(max(0.0, b.start - a.end) * 1e-9)
    return gaps


def top_ops(trace: Trace, n: int = 10) -> list[list]:
    """The `n` ops with the most device time, summed over chips."""
    total: dict[str, float] = {}
    for d in trace.chips:
        for e in trace.ops[d]:
            total[e.label] = total.get(e.label, 0.0) + e.dur * 1e-9
    return [[k, v] for k, v in sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def _host_label(host: list[Event], a: float, b: float) -> str:
    """The host event overlapping [a, b) most; the shorter one on a tie."""
    best, key = "no host event", (0.0, 0.0)
    for e in host:
        if e.start >= b:
            break
        if e.dur > HOST_SPAN_MAX_NS:
            continue
        overlap = min(b, e.end) - max(a, e.start)
        if overlap > 0 and (overlap, -e.dur) > key:
            best, key = e.name, (overlap, -e.dur)
    return best


def idle_gaps(trace: Trace, n: int = 10) -> list[list]:
    """The `n` longest holes between ops on any chip, named by host activity."""
    holes = []
    for d in trace.chips:
        iv = union(trace.ops[d])
        holes.extend((b0, a1) for (_, b0), (a1, _) in zip(iv, iv[1:]))
    holes.sort(key=lambda h: h[0] - h[1])
    return [[_host_label(trace.host, a, b), (b - a) * 1e-9] for a, b in holes[:n]]
