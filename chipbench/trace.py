"""From a JAX profiler trace to the numbers the per-layer metrics read.

Two stages. :func:`load_events` reads an ``.xplane.pb`` file into plain
lists (it needs JAX); the reductions below work on those lists alone, so
the tests check them on a small recorded trace kept as JSON.

Event lists hold ``[name, start_ns, duration_ns]`` triples:

* ``ops``: the device's operations (the TPU plane's "XLA Ops" line);
* ``modules``: the device's program runs ("XLA Modules"), named
  ``jit_<function>`` plus a suffix;
* ``host``: the benchmark's own host spans, whose names start with
  ``chipbench.``; the one named ``chipbench.window`` bounds the window.

The device's clock and the host's differ by about a millisecond, so the
harness runs a tiny program, ``jit_chipbench_mark``, just inside each
end of the window: its runs bound the window on the device's clock, and
the gap between a mark's run and its host span aligns the host spans.
The device's trace buffer holds a bounded number of events, and
:func:`load_events` reads at most ``MAX_OPS`` operations a device. Where
a mark is missing or the reading stopped, the window is what the
device's events still cover (``truncated``).
"""
from __future__ import annotations

import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

SPAN_PREFIX = "chipbench."
WINDOW_SPAN = "chipbench.window"
MARK_SPAN = "chipbench.mark"
MARK_PROGRAM = "jit_chipbench_mark"
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
# operations read per device: enough for a few seconds of the busiest
# cell, few enough that the reading takes well under a minute
MAX_OPS = 1_000_000


def load_events(path: str) -> dict:
    """``{"devices": {ordinal: {"ops": [...], "modules": [...]}},
    "host": [...]}`` from one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices: Dict[int, dict] = {}
    host: List[list] = []
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            dev = devices.setdefault(int(m.group(1)),
                                     {"ops": [], "modules": [],
                                      "capped": False})
            for line in plane.lines:
                key = {"XLA Ops": "ops",
                       "XLA Modules": "modules"}.get(line.name)
                if not key:
                    continue
                names: Dict[str, str] = {}
                for e in line.events:
                    if key == "ops" and len(dev["ops"]) >= MAX_OPS:
                        dev["capped"] = True
                        break
                    name = e.name
                    dev[key].append([names.setdefault(name, name),
                                     e.start_ns, e.duration_ns])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([e.name, e.start_ns, e.duration_ns]
                            for e in line.events
                            if e.name.startswith(SPAN_PREFIX))
    return {"devices": devices, "host": host}


def merge(intervals: Iterable[Tuple[float, float]]) -> List[List[float]]:
    """Union of ``(start, end)`` intervals, sorted and disjoint."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(intervals: Sequence[Sequence[float]], lo: float, hi: float
         ) -> List[List[float]]:
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if e > lo and s < hi]


def _first_device(events: dict) -> Optional[dict]:
    devs = events["devices"]
    return devs[min(devs)] if devs else None


def window_of(events: dict) -> Tuple[float, float]:
    """Start and end (ns, device clock) of the traced window."""
    return _window(events)[:2]


def _window(events: dict) -> Tuple[float, float, float, bool]:
    """(start, end, host-to-device clock offset, truncated), computed
    once per trace."""
    if "_window" not in events:
        events["_window"] = _find_window(events)
    return events["_window"]


def _find_window(events: dict) -> Tuple[float, float, float, bool]:
    spans = [(s, s + d) for n, s, d in events["host"] if n == WINDOW_SPAN]
    if not spans:
        raise ValueError("the trace holds no chipbench.window span")
    lo, hi = min(s for s, _ in spans), max(e for _, e in spans)
    dev = _first_device(events)
    marks = sorted((s, s + d) for n, s, d in (dev or {}).get("modules", [])
                   if n.startswith(MARK_PROGRAM))
    host_marks = sorted(s for n, s, _ in events["host"] if n == MARK_SPAN)
    if not marks or not host_marks:
        return lo, hi, 0.0, False
    # pair a device mark with its host span: the one at the window's
    # start where it is there, else the one at its end
    near_start = marks[0][0] - host_marks[0]
    offset = near_start if abs(near_start) < 5e8 \
        else marks[-1][0] - host_marks[-1]
    lo, hi = lo + offset, hi + offset
    start_mark = abs(marks[0][0] - lo) < 5e8
    end_mark = abs(marks[-1][1] - hi) < 5e8
    ops = dev["ops"]
    lo = marks[0][0] if start_mark else max(lo, min(s for _, s, _ in ops))
    hi = marks[-1][1] if end_mark else hi
    cut = dev.get("capped", False) or not end_mark
    if cut:
        hi = min(hi, max(s + d for _, s, d in ops))
    return lo, hi, offset, cut or not start_mark


def busy_intervals(ops: Sequence[Sequence], lo: float, hi: float
                   ) -> List[List[float]]:
    return clip(merge((s, s + d) for _, s, d in ops), lo, hi)


def busy_s(events: dict) -> float:
    """Seconds in which an operation ran, averaged over the devices."""
    lo, hi = window_of(events)
    devs = events["devices"].values()
    if not devs:
        return 0.0
    return sum(sum(e - s for s, e in busy_intervals(d["ops"], lo, hi))
               for d in devs) / len(devs) / 1e9


def module_stats(events: dict, prefix: str) -> Dict[str, float]:
    """Count and device seconds of the program runs named ``prefix``*,
    inside the window, summed over the devices."""
    lo, hi = window_of(events)
    n, total = 0, 0.0
    for dev in events["devices"].values():
        for name, s, d in dev["modules"]:
            if name.startswith(prefix) and s >= lo and s < hi:
                n += 1
                total += d
    return {"count": n, "seconds": total / 1e9}


def top_ops(events: dict, n: int = 10) -> List[list]:
    """The ``n`` device operations that took most time in the window."""
    lo, hi = window_of(events)
    tot: Dict[str, float] = {}
    for dev in events["devices"].values():
        for name, s, d in dev["ops"]:
            if s >= lo and s < hi:
                tot[name] = tot.get(name, 0.0) + d
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in ranked]


def idle_gaps(events: dict, n: int = 10) -> List[list]:
    """Idle time of device 0 in the window, summed by what the host was
    doing: each gap goes to the host span (other than the window) that
    overlaps it most, or to ``"none"``."""
    lo, hi = window_of(events)
    devs = events["devices"]
    if not devs:
        return []
    busy = busy_intervals(devs[min(devs)]["ops"], lo, hi)
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    off = _window(events)[2]
    spans = sorted((s + off, s + d + off, name)
                   for name, s, d in events["host"]
                   if name not in (WINDOW_SPAN, MARK_SPAN))
    tot: Dict[str, float] = {}
    for gs, ge in gaps:
        best, who = 0.0, "none"
        for s, e, name in spans:
            if s >= ge:
                break
            ov = min(e, ge) - max(s, gs)
            if ov > best:
                best, who = ov, name
        tot[who] = tot.get(who, 0.0) + (ge - gs)
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in ranked]


def reduce(events: dict) -> dict:
    """Everything the readers and the result line take from a trace."""
    lo, hi, _, cut = _window(events)
    return {"window_s": (hi - lo) / 1e9, "busy_s": busy_s(events),
            "devices": len(events["devices"]), "truncated": cut,
            "device_ops": sum(len(d["ops"])
                              for d in events["devices"].values()),
            "top_ops": top_ops(events), "idle_gaps": idle_gaps(events),
            "events": events}


def module_ms(trace: Optional[dict], prefix: str) -> Optional[float]:
    """Mean device milliseconds of one run of the programs ``prefix``*,
    or None where the window ran none."""
    if not trace:
        return None
    st = module_stats(trace["events"], prefix)
    if not st["count"]:
        return None
    return 1e3 * st["seconds"] / st["count"]
