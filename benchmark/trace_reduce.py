"""From a profiler trace (``.xplane.pb``) to numbers: device busy union, idle
share, time per operation, time of operations matching a pattern (the
collectives), and the idle gaps attributed to what the host was doing.

Layout on a TPU v5e (jax 0.9, read from a recorded trace, see
``tests/data/probe_v5e.xplane.pb``): one plane ``/device:TPU:<n>`` per chip
with the lines ``XLA Modules`` (one event per executed program, named
``jit_<fn>(<hash>)``), ``XLA Ops`` (one event per HLO operation, named by its
HLO text ``%<op> = ...``) and ``Async XLA Ops``; one plane ``/host:CPU`` whose
``python`` line (and other thread lines) carry ``TraceAnnotation`` names.
All times are nanoseconds on one axis.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
_MODULE_NAME = re.compile(r"^(.*?)\(\d+\)$")
_OP_NAME = re.compile(r"^%?([\w.\-]+)")
_OP_SUFFIX = re.compile(r"[.\-]\d+$")
# Names the host planes carry for the XLA runtime's own work are CamelCase,
# have "::", "(" or spaces; a layer annotation is "<layer>.<what>".
ANNOTATION = re.compile(r"^[a-z][a-z0-9_]*\.[a-z0-9_.]+$")


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Sorted, disjoint cover of ``intervals``."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def total(intervals: Sequence[Interval]) -> float:
    return float(sum(b - a for a, b in intervals))


def op_name(hlo_text: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion``."""
    m = _OP_NAME.match(hlo_text.strip())
    name = m.group(1) if m else hlo_text.strip()[:40]
    return _OP_SUFFIX.sub("", name)


def module_name(text: str) -> str:
    m = _MODULE_NAME.match(text)
    return m.group(1) if m else text


class Trace:
    """Events of one recorded trace, by device, plus the host annotations."""

    def __init__(self, ops: Dict[int, list], modules: Dict[int, list],
                 annotations: list):
        self.ops = ops                  # device -> [(start, end, hlo text)]
        self.modules = modules          # device -> [(start, end, name)]
        self.annotations = annotations  # [(start, end, name)]

    @classmethod
    def from_file(cls, path: str) -> "Trace":
        from jax.profiler import ProfileData
        data = ProfileData.from_file(path)
        ops: Dict[int, list] = {}
        modules: Dict[int, list] = {}
        annotations = []
        for plane in data.planes:
            m = DEVICE_PLANE.match(plane.name)
            if m:
                dev = int(m.group(1))
                for line in plane.lines:
                    if line.name not in (OPS_LINE, MODULES_LINE):
                        continue
                    dest = ops if line.name == OPS_LINE else modules
                    dest.setdefault(dev, []).extend(
                        (float(e.start_ns), float(e.start_ns + e.duration_ns),
                         e.name) for e in line.events)
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    annotations.extend(
                        (float(e.start_ns), float(e.start_ns + e.duration_ns),
                         e.name) for e in line.events
                        if ANNOTATION.match(e.name))
        for events in list(ops.values()) + list(modules.values()):
            events.sort()
        annotations.sort()
        return cls(ops, modules, annotations)

    @classmethod
    def from_dir(cls, trace_dir: str) -> "Trace":
        return cls.from_file(find_xplane(trace_dir))

    # -- device time -------------------------------------------------------
    def devices(self) -> List[int]:
        return sorted(set(self.ops) | set(self.modules))

    def _events(self, device: int) -> list:
        return self.ops.get(device) or self.modules.get(device) or []

    def busy(self, device: int, window: Optional[Interval] = None
             ) -> List[Interval]:
        """Union of the intervals in which an operation ran on ``device``,
        clipped to ``window`` (ns) where one is given."""
        spans = [(a, b) for a, b, _ in self._events(device)]
        if window is not None:
            t0, t1 = window
            spans = [(max(a, t0), min(b, t1)) for a, b in spans
                     if b > t0 and a < t1]
        return union(spans)

    def busy_s(self, window: Optional[Interval] = None) -> float:
        """Busy seconds, averaged over the devices in the trace."""
        devs = self.devices()
        if not devs:
            return 0.0
        return sum(total(self.busy(d, window)) for d in devs) \
            / len(devs) / 1e9

    def idle_share(self, window: Interval) -> Optional[float]:
        """1 - busy / window, the window in ns on the trace's axis."""
        if not self.devices() or window[1] <= window[0]:
            return None
        return 1.0 - self.busy_s(window) / ((window[1] - window[0]) / 1e9)

    def _module_of(self, device: int):
        mods = self.modules.get(device, [])
        starts = [m[0] for m in mods]
        import bisect

        def lookup(t: float) -> str:
            i = bisect.bisect_right(starts, t) - 1
            if i >= 0 and mods[i][0] <= t <= mods[i][1]:
                return module_name(mods[i][2])
            return "no_module"
        return lookup

    def op_seconds(self, device: int = 0) -> Dict[str, float]:
        """Seconds per operation, named ``<op>_in_<module>``."""
        lookup = self._module_of(device)
        out: Dict[str, float] = {}
        for a, b, text in self.ops.get(device, []):
            key = f"{op_name(text)}_in_{lookup(a)}"
            out[key] = out.get(key, 0.0) + (b - a) / 1e9
        if not out:     # a trace with programs but no per-op line
            for a, b, text in self.modules.get(device, []):
                key = module_name(text)
                out[key] = out.get(key, 0.0) + (b - a) / 1e9
        return out

    def matching_seconds(self, pattern: str, device: int = 0,
                         where: str = "op",
                         window: Optional[Interval] = None) -> float:
        """Union time on ``device`` of the operations (``where='op'``: the
        HLO operation's name; ``'module'``: the program's name) that match
        the regular expression ``pattern``."""
        rx = re.compile(pattern)
        if where == "module":
            hits = [(a, b) for a, b, t in self.modules.get(device, [])
                    if rx.search(module_name(t))]
        else:
            hits = [(a, b) for a, b, t in self.ops.get(device, [])
                    if rx.search(op_name(t))]
        if window is not None:
            t0, t1 = window
            hits = [(max(a, t0), min(b, t1)) for a, b in hits
                    if b > t0 and a < t1]
        return total(union(hits)) / 1e9

    # -- idle gaps ---------------------------------------------------------
    def idle_gaps(self, t0: float, t1: float, device: int = 0,
                  skip: Sequence[str] = ()) -> Dict[str, float]:
        """Idle seconds of ``device`` inside [t0, t1] (ns), by the host
        annotation that overlaps each gap most (the shortest such, so a
        nested annotation wins over the one around it); ``no_span`` where
        none does."""
        busy = self.busy(device, (t0, t1))
        gaps, cur = [], t0
        for a, b in busy:
            if a > cur:
                gaps.append((cur, a))
            cur = max(cur, b)
        if cur < t1:
            gaps.append((cur, t1))
        out: Dict[str, float] = {}
        ann = [e for e in self.annotations if e[2] not in skip]
        for g0, g1 in gaps:
            best, best_key = "no_span", (0.0, 0.0)
            for a, b, name in ann:
                if a >= g1:
                    break
                ov = min(b, g1) - max(a, g0)
                if ov <= 0:
                    continue
                key = (ov, -(b - a))
                if key > best_key:
                    best, best_key = name, key
            out[best] = out.get(best, 0.0) + (g1 - g0) / 1e9
        return out

    def span(self) -> Optional[Interval]:
        """First start and last end of anything recorded."""
        every = [e for d in self.devices() for e in self._events(d)]
        every += self.annotations
        if not every:
            return None
        return min(e[0] for e in every), max(e[1] for e in every)

    def annotation_window(self, name: str) -> Optional[Interval]:
        hits = [(a, b) for a, b, n in self.annotations if n == name]
        if not hits:
            return None
        return min(a for a, _ in hits), max(b for _, b in hits)


def top(entries: Dict[str, float], n: int = 10) -> list:
    return [[k, v] for k, v in sorted(entries.items(),
                                      key=lambda kv: -kv[1])[:n]]
