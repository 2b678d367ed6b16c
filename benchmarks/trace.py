"""From a profiler trace (``.xplane.pb``) to device busy/idle time, kernel
time and labelled idle gaps.

Two steps, kept apart so that the arithmetic can be checked on a small
recorded list of events (tests/test_trace_reduction.py):

``load_events``  reads the xplane with nothing but JAX and returns plain
    tuples ``(name, start_s, duration_s)``: the device's operations, and
    what the host's Python thread was doing.
``reduce``       is pure arithmetic on those tuples.

Facts this depends on, from the by-hand look at the first traces (PERF.md
section 6; TPU v5 lite, jax 0.9.0):

- the device's plane is ``/device:TPU:<n>``; its line ``XLA Ops`` has one
  event per executed HLO instruction, named by the instruction's whole text
  (``%_tiled_apply.21 = f32[5,16,128]{...} custom-call(...)``).  Control
  flow (``%while.105``, ``%cond.23``) is an event that spans the events of
  its body on the same line, so busy time is the union of the LEAVES: the
  events that contain no other.  ``XLA Modules`` (one event per executed
  program) and ``Async XLA Ops`` (copies in flight) are not read;
- the tile kernel's events are the ``custom-call`` instructions named after
  the jitted wrapper ``_tiled_apply`` (the ``pallas_call`` inside it has no
  ``name=``): one launch per product, forward or backward;
- the host's plane is ``/host:CPU``; its line ``python`` carries the
  harness's ``TraceAnnotation`` names (``grid``) and, from the profiler's
  Python tracer, one event per Python call (``$problem.py:236 grid_loop``),
  on the same clock as the device plane.
"""

from __future__ import annotations

import dataclasses
import glob
import os

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE_PREFIX = "/host:CPU"
HOST_LINE = "python"
#: The tile kernel's device events: custom calls named after this function.
KERNEL_MARK = "_tiled_apply"
KERNEL_OPCODE = "custom-call("
#: Idle gaps shorter than this lie between two operations of one program;
#: they are summed under one label and not laid against the host's calls.
SHORT_GAP_S = 100e-6
SHORT_GAP_LABEL = "between_device_ops"
#: The harness's annotation around each run_grid call.
WINDOW_ANNOTATION = "grid"


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load_events(path: str) -> dict:
    """``{"device": {plane: [(name, start_s, dur_s)]}, "host": [...]}``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device: dict[str, list] = {}
    host: list = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device[plane.name] = [
                        (ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9)
                        for ev in line.events]
        elif plane.name.startswith(HOST_PLANE_PREFIX):
            for line in plane.lines:
                if line.name == HOST_LINE:
                    host.extend(
                        (ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9)
                        for ev in line.events)
    return {"device": device, "host": host}


def union(intervals):
    """Merged ``[(start, end)]`` of possibly overlapping intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                 # mean over the device planes
    kernel_durations_s: list      # every tile-kernel event, all planes
    device_ops: list              # [(name, total seconds)], most time first
    idle_gaps: list               # [(what the host did, idle seconds)]
    planes: int

    def breakdown(self) -> dict:
        return {"device_ops": [[n, s] for n, s in self.device_ops[:10]],
                "idle_gaps": [[n, s] for n, s in self.idle_gaps[:10]]}


def leaves(events):
    """The events that contain no other event of the same line (an event
    that merely overlaps the next one's start is still a leaf)."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    parent = [False] * len(events)
    stack = []  # (end, index) of the events still open
    for i in order:
        _name, start, dur = events[i]
        while stack and stack[-1][0] < start + dur:
            stack.pop()  # ended before this one does: not its container
        if stack:
            parent[stack[-1][1]] = True
        stack.append((start + dur, i))
    return [e for e, is_parent in zip(events, parent) if not is_parent]


def short_name(name: str) -> str:
    """``%_tiled_apply.21 = f32[...] custom-call(...)`` -> ``_tiled_apply.21``."""
    return name.split(" = ", 1)[0].lstrip("%$")[:64]


def is_kernel(name: str) -> bool:
    return KERNEL_OPCODE in name and KERNEL_MARK in name.split(" = ", 1)[0]


def _label(mid, host_events):
    """What the host's Python thread was doing at ``mid``: the innermost
    call that spans it; between two ``grid`` annotations the harness is
    starting the grid again."""
    best = None
    for name, start, dur in host_events:
        if start <= mid <= start + dur and (best is None or dur < best[1]):
            best = (name, dur)
    return short_name(best[0]) if best else "grid_restart"


def reduce(events: dict, window=None) -> Reduced:
    """``window`` is ``(start_s, end_s)`` on the trace's clock; by default
    from the first to the last device operation."""
    planes = {p: leaves(evs) for p, evs in events["device"].items()}
    if not any(planes.values()):
        raise ValueError("no device operation in the trace: nothing ran "
                         "on a TPU")
    if window is None:
        lo = min(e[1] for evs in planes.values() for e in evs)
        hi = max(e[1] + e[2] for evs in planes.values() for e in evs)
    else:
        lo, hi = window
    host = [e for e in events["host"] if e[1] < hi and e[1] + e[2] > lo]
    busy, kernels, totals, gaps = [], [], {}, {}
    for evs in planes.values():
        inside = [e for e in evs if e[1] < hi and e[1] + e[2] > lo]
        merged = union((max(s, lo), min(s + d, hi)) for _n, s, d in inside)
        busy.append(sum(e - s for s, e in merged))
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                label = (SHORT_GAP_LABEL if b - a < SHORT_GAP_S
                         else _label(0.5 * (a + b), host))
                gaps[label] = gaps.get(label, 0.0) + (b - a)
        for name, _s, d in inside:
            key = short_name(name)
            totals[key] = totals.get(key, 0.0) + d
            if is_kernel(name):
                kernels.append(d)
    return Reduced(
        window_s=hi - lo,
        busy_s=sum(busy) / len(busy),
        kernel_durations_s=kernels,
        device_ops=sorted(totals.items(), key=lambda kv: -kv[1]),
        idle_gaps=sorted(gaps.items(), key=lambda kv: -kv[1]),
        planes=len(planes),
    )


def reduce_dir(trace_dir: str) -> Reduced:
    """Reduce the newest trace under ``trace_dir``.  The window on the
    trace's clock is what the harness's annotations span: from the first
    ``grid`` annotation's start to the last one's end."""
    events = load_events(find_xplane(trace_dir))
    grids = [e for e in events["host"] if e[0] == WINDOW_ANNOTATION]
    window = None
    if grids:
        window = (min(e[1] for e in grids), max(e[1] + e[2] for e in grids))
    return reduce(events, window)
