"""From a JAX profiler trace to the numbers the benchmark reports.

``load`` reads an ``.xplane.pb`` with ``jax.profiler.ProfileData`` and
keeps two things: the operations that ran on each device, and the
benchmark's own ``TraceAnnotation`` spans on the host (named ``bench.``);
the runtime's host events are left out. ``reduce`` turns them into:

- ``busy_s``: the union of the device's operation intervals inside the
  window, in seconds, averaged over the devices;
- ``window_s`` and ``idle_share`` (1 - busy / window);
- ``device_ops``: the operations that took the most device time, by name
  (the HLO instruction's name, without its text);
- ``idle_gaps``: the device's idle time inside the window, split by what
  the host was doing meanwhile: each idle instant goes to the innermost
  host span covering it, or to ``client`` where none does.

On a TPU the device operations are the events of each ``/device:TPU:n``
plane's ``XLA Ops`` line. A trace recorded on the CPU has no device
plane; its operations run on the CPU client's threads (``tf_XLA...``
lines of ``/host:CPU``), which stand in for one device so the reduction
can be tested off the chip. A CPU trace never yields a device metric:
the harness refuses to report one from it.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

Interval = Tuple[str, float, float]  # (name, start_ns, end_ns)

DEVICE_PLANE_PREFIX = "/device:TPU:"
DEVICE_OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
CPU_OPS_LINE_PREFIX = "tf_XLA"
CPU_SKIP_PREFIX = "ThreadpoolListener"
HOST_PREFIX = "bench."


@dataclass
class Profile:
    device_ops: Dict[str, List[Interval]] = field(default_factory=dict)
    host: List[Interval] = field(default_factory=list)
    on_cpu: bool = False


def load(path: str) -> Profile:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    prof = Profile()
    host_plane = None
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            for line in plane.lines:
                if line.name == DEVICE_OPS_LINE:
                    prof.device_ops[plane.name] = [
                        (op_name(e.name), e.start_ns,
                         e.start_ns + e.duration_ns)
                        for e in line.events]
        elif plane.name == HOST_PLANE:
            host_plane = plane
    if host_plane is not None:
        cpu_ops: List[Interval] = []
        for line in host_plane.lines:
            cpu = line.name.startswith(CPU_OPS_LINE_PREFIX)
            for e in line.events:
                iv = (e.name, e.start_ns, e.start_ns + e.duration_ns)
                if cpu:
                    if e.duration_ns > 0 and \
                            not e.name.startswith(CPU_SKIP_PREFIX):
                        cpu_ops.append(iv)
                elif e.name.startswith(HOST_PREFIX):
                    prof.host.append(iv)
        if not prof.device_ops:
            prof.on_cpu = True
            prof.device_ops[HOST_PLANE] = cpu_ops
    return prof


def op_name(event_name: str) -> str:
    """``%sort.6 = (f32[...]) sort(...)`` -> ``sort.6``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def clip(intervals, lo: float, hi: float) -> List[Interval]:
    out = []
    for name, s, e in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out.append((name, s, e))
    return out


def union(intervals) -> List[Tuple[float, float]]:
    """The disjoint, sorted union of the intervals."""
    merged: List[List[float]] = []
    for _, s, e in sorted(intervals, key=lambda iv: iv[1]):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def gaps(busy: List[Tuple[float, float]], lo: float,
         hi: float) -> List[Tuple[float, float]]:
    """The complement of ``busy`` inside [lo, hi]."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def innermost(host: List[Interval]) -> List[Interval]:
    """The host's timeline cut where any span starts or ends, each piece
    named by the shortest span that covers it; uncovered time is left
    out."""
    import heapq

    bounds = sorted({t for _, s, e in host for t in (s, e)})
    starts = sorted(host, key=lambda iv: iv[1])
    heap: List[tuple] = []
    out: List[Interval] = []
    i = 0
    for a, b in zip(bounds, bounds[1:]):
        while i < len(starts) and starts[i][1] <= a:
            name, s, e = starts[i]
            heapq.heappush(heap, (e - s, e, name))
            i += 1
        while heap and heap[0][1] <= a:
            heapq.heappop(heap)
        # A shorter span may have ended while a longer one still covers.
        live = [h for h in heap if h[1] > a]
        if len(live) != len(heap):
            heap = live
            heapq.heapify(heap)
        if heap:
            out.append((heap[0][2], a, b))
    return out


def attribute(gap_list, host: List[Interval]) -> Dict[str, float]:
    """Idle nanoseconds by what the host was doing: each instant of a gap
    goes to the innermost host span covering it, or to ``client``."""
    by_name: Dict[str, float] = {}
    pieces = innermost(host)
    j = 0
    for s, e in sorted(gap_list):
        covered = 0.0
        while j < len(pieces) and pieces[j][2] <= s:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][1] < e:
            name, a, b = pieces[k]
            overlap = min(b, e) - max(a, s)
            if overlap > 0:
                by_name[name] = by_name.get(name, 0.0) + overlap
                covered += overlap
            k += 1
        if e - s > covered:
            by_name["client"] = by_name.get("client", 0.0) + (e - s - covered)
    return by_name


def top(totals: Dict[str, float], k: int = 10) -> List[list]:
    return [[name, ns / 1e9] for name, ns in
            sorted(totals.items(), key=lambda kv: -kv[1])[:k]]


def reduce(prof: Profile, lo: float, hi: float,
           host: Optional[List[Interval]] = None) -> dict:
    """The window [lo, hi] (trace nanoseconds) of ``prof``. ``host`` adds
    spans (already on the trace's clock) to the profile's own host
    annotations for the gap attribution; a span that covers the whole
    window (the window's own annotation) says nothing and is left out."""
    host_spans = [iv for iv in clip(prof.host + list(host or []), lo, hi)
                  if iv[1] > lo or iv[2] < hi]
    busy_total = 0.0
    op_totals: Dict[str, float] = {}
    gap_totals: Dict[str, float] = {}
    for ops in prof.device_ops.values():
        ops = clip(ops, lo, hi)
        busy = union(ops)
        busy_total += sum(e - s for s, e in busy)
        for name, s, e in ops:
            op_totals[name] = op_totals.get(name, 0.0) + (e - s)
        for name, ns in attribute(gaps(busy, lo, hi), host_spans).items():
            gap_totals[name] = gap_totals.get(name, 0.0) + ns
    n_dev = max(len(prof.device_ops), 1)
    window_s = (hi - lo) / 1e9
    busy_s = busy_total / n_dev / 1e9
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "idle_share": 1.0 - busy_s / window_s if window_s > 0 else None,
        "device_ops": top(op_totals),
        "idle_gaps": top({k: v / n_dev for k, v in gap_totals.items()}),
    }


def find(host: List[Interval], name: str) -> Optional[Interval]:
    """The first host annotation called ``name``."""
    for iv in host:
        if iv[0] == name:
            return iv
    return None
