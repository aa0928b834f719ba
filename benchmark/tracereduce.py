"""From a profiler trace to numbers: the one reduction every PR shares.

``load_events`` turns an ``.xplane.pb`` into plain
``(plane, name, start_ns, dur_ns)`` tuples — ``plane`` is ``"device:<n>"``
for an operation that ran on chip n, ``"async:<n>"`` for the start-to-done
span of an asynchronous collective there, and ``"host"`` for a host span — and
``reduce`` works on those tuples alone, so it is tested without a chip.
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

Event = Tuple[str, str, float, float]

WINDOW_SPAN = "bench:window"  # the runner's annotation around the traced part
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")
DEVICE_OP_LINE = "XLA Ops"  # the device plane's line of single operations
MOSAIC = "tpu_custom_call"       # the custom-call target of a Pallas kernel
ASYNC_OP_LINE = "Async XLA Ops"  # start-to-done spans of asynchronous ones
ATTRIBUTED_GAPS = 200       # the longest gaps get a host span's name
_SHAPE = re.compile(r"([a-z]+[0-9]*\[[0-9,]*\])")


def op_kind(name: str) -> str:
    """``all-reduce.12`` / ``fusion:convert_fusion f32[8]`` -> ``all-reduce`` /
    ``fusion``: the opcode of an event's name."""
    base = name.lstrip("%").split(" ", 1)[0].split(":", 1)[0]
    if base == MOSAIC:
        return base
    return re.sub(r"[.\-_]?\d+$", "", re.sub(r"-(start|done)(\.\d+)?$", "", base))


def is_collective(name: str) -> bool:
    return op_kind(name).startswith(COLLECTIVES)


def is_mosaic(name: str) -> bool:
    """A Pallas (Mosaic) kernel: a custom call whose target is
    ``tpu_custom_call`` (XLA's own custom calls are markers of a nanosecond)."""
    return op_kind(name) == MOSAIC


_HLO = re.compile(r"=\s*(.*?)\s+([a-z][a-z0-9\-]*)\(")


def event_name(name: str, stats: dict) -> str:
    """``<opcode>:<instruction> <shapes it writes>`` from the HLO text that a
    TPU trace gives as the event's name (or in a stat):
    ``tpu_custom_call:jvp__ bf16[256,512,64] f32[256,512,1]``. Durations then
    add up by kind and shape, and a Mosaic call, which XLA names after the jax
    name stack, is known by its target. Without HLO text: the
    category the trace gives, else the instruction's own name."""
    texts = [name] + [v for v in stats.values() if isinstance(v, str)]
    for text in texts:
        m = _HLO.search(text) if " = " in text else None
        if m:
            base = re.sub(r"[.]\d+$", "", text.split(" = ", 1)[0].lstrip("%"))
            shapes = _SHAPE.findall(m.group(1))[:4]
            opcode = MOSAIC if MOSAIC in text else m.group(2)
            return f"{opcode}:{base} " + " ".join(shapes)
    base = re.sub(r"[.]\d+$", "", name.lstrip("%"))
    for key in ("hlo_category", "category"):
        if isinstance(stats.get(key), str):
            return f"{stats[key]}:{base}"
    return base


def load_events(xplane_path: str) -> List[Event]:
    from jax.profiler import ProfileData

    events: List[Event] = []
    data = ProfileData.from_file(xplane_path)
    for plane in data.planes:
        m = re.match(r"/device:TPU:(\d+)$", plane.name)
        if m:
            for line in plane.lines:
                # an asynchronous collective is a short start and a short done
                # on the op line; its span, start to done, is on the async
                # line and overlaps compute, so it never counts as busy time
                if line.name == DEVICE_OP_LINE:
                    plane_name = f"device:{m.group(1)}"
                elif line.name == ASYNC_OP_LINE:
                    plane_name = f"async:{m.group(1)}"
                else:
                    continue
                for e in line.events:
                    name = event_name(e.name, dict(e.stats))
                    if plane_name.startswith("device:") or is_collective(name):
                        events.append((plane_name, name, float(e.start_ns),
                                       float(e.duration_ns)))
        elif plane.name.startswith("/host:") and "metadata" not in plane.name:
            for line in plane.lines:
                for e in line.events:
                    if e.duration_ns > 0:
                        events.append(("host", e.name, float(e.start_ns),
                                       float(e.duration_ns)))
    return events


def describe(xplane_path: str, per_line: int = 6) -> str:
    """Planes, lines and a few events with their stats: what to read by
    hand before trusting the reduction on a new kind of trace."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(xplane_path).planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            evs = list(line.events)
            out.append(f"  LINE {line.name} events={len(evs)}")
            for e in evs[:per_line]:
                stats = {k: (v if not isinstance(v, str) else v[:200])
                         for k, v in e.stats}
                out.append(f"    {e.name[:120]} start={e.start_ns} "
                           f"dur={e.duration_ns} stats={stats}")
    return "\n".join(out)


def _merge(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _clip(s: float, e: float, lo: float, hi: float) -> Tuple[float, float]:
    return max(s, lo), min(e, hi)


def reduce(events: Sequence[Event], n_devices: int = 1) -> Optional[dict]:
    """Busy and idle time, per-name durations, Mosaic and collective shares,
    and the longest idle gaps named by the innermost host span over them.

    The window is the ``bench:window`` host span where the trace has one and
    device operations fall inside it, else the extent of the device
    operations. Busy time is the union of operation intervals per chip,
    averaged over the chips seen; names, shares and gaps are chip 0's."""
    device = [e for e in events if e[0].startswith("device:")]
    if not device:
        return None
    host = [e for e in events if e[0] == "host"]
    lo = min(e[2] for e in device)
    hi = max(e[2] + e[3] for e in device)
    spans = [e for e in host if e[1] == WINDOW_SPAN]
    if spans:
        w_lo, w_hi = spans[0][2], spans[0][2] + spans[0][3]
        inside = sum(1 for e in device if w_lo <= e[2] <= w_hi)
        if inside * 2 >= len(device):  # same clock: trust the host span
            lo, hi = w_lo, w_hi
    window_ns = hi - lo

    planes = sorted({e[0] for e in device})
    merged = {}
    for plane in planes:
        ivs = [_clip(e[2], e[2] + e[3], lo, hi) for e in device if e[0] == plane]
        merged[plane] = _merge([i for i in ivs if i[1] > i[0]])
    busy_ns = [sum(e - s for s, e in merged[p]) for p in planes]
    first = planes[0]
    ops: Dict[str, float] = {}
    mosaic_ns = collective_ns = 0.0
    mosaic: Dict[str, List[float]] = {}
    for plane, name, start, dur in device:
        if plane != first:
            continue
        s, e = _clip(start, start + dur, lo, hi)
        if e <= s:
            continue
        ops[name] = ops.get(name, 0.0) + (e - s)
        if is_collective(name):
            collective_ns += e - s
        elif is_mosaic(name):
            mosaic_ns += e - s
            if e - s == dur:  # whole calls only: a clipped one is no sample
                mosaic.setdefault(name, []).append(dur)
    merged0, busy0 = merged[first], busy_ns[0]
    for plane, name, start, dur in events:
        if plane == "async:" + first.split(":")[1] and is_collective(name):
            s, e = _clip(start, start + dur, lo, hi)
            collective_ns += max(0.0, e - s)

    # idle gaps on chip 0, the longest ones named by the host's innermost span
    gaps = []
    edge = lo
    for s, e in merged0:
        if s > edge:
            gaps.append((edge, s))
        edge = max(edge, e)
    if hi > edge:
        gaps.append((edge, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    by_span: Dict[str, float] = {}
    named = [h for h in host if h[1] != WINDOW_SPAN]
    h_start = np.array([h[2] for h in named])
    h_dur = np.array([h[3] for h in named])
    for s, e in gaps[:ATTRIBUTED_GAPS]:
        mid = (s + e) / 2
        key = "(no host span)"
        if named:
            over = (h_start <= mid) & (h_start + h_dur >= mid)
            if over.any():
                key = named[int(np.argmin(np.where(over, h_dur, np.inf)))][1]
        by_span[key] = by_span.get(key, 0.0) + (e - s)
    rest = sum(e - s for s, e in gaps[ATTRIBUTED_GAPS:])
    if rest:
        by_span["(shorter gaps)"] = rest

    def top(d):
        return [[k, v / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])]

    return {
        "window_s": window_ns / 1e9,
        "busy_s": sum(busy_ns) / len(busy_ns) / 1e9,
        "devices_seen": len(planes),
        "devices_expected": n_devices,
        "idle_share": 1.0 - (sum(busy_ns) / len(busy_ns)) / window_ns,
        "busy0_s": busy0 / 1e9,
        "device_ops": top(ops),
        "idle_gaps": top(by_span),
        "mosaic_s": mosaic_ns / 1e9,
        "mosaic_calls": {k: {"calls": len(v), "seconds": sum(v) / 1e9}
                         for k, v in mosaic.items()},
        "collective_s": collective_ns / 1e9,
    }
