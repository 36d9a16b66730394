"""Reduction of one profiler trace to the numbers the per-layer metrics
read.

A traced run records the measured window with ``jax.profiler`` and
marks it, each batch and each wrapped call of the program with
``TraceAnnotation`` host spans (``WINDOW``, ``BATCH``, and the names in
``harness.SPANS``). ``load`` reads the ``.xplane.pb`` the profiler
wrote with nothing but JAX: device planes (``/device:TPU:<n>``) give the
operations that ran on each chip (line ``XLA Ops``) and the compiled
programs they belong to (line ``XLA Modules``), host planes give the
spans. Everything is clipped to the window span.
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from collections import defaultdict
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Set, Tuple

WINDOW = "bench_window"
BATCH = "bench_batch"
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")

Interval = Tuple[float, float, str]     # (start_ns, end_ns, name)


@dataclasses.dataclass
class Trace:
    window: Tuple[float, float]                  # ns, from the WINDOW span
    ops: Dict[int, List[Interval]]               # per device: XLA Ops
    modules: Dict[int, List[Interval]]           # per device: XLA Modules
    spans: List[Interval]                        # host spans we named

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    @property
    def n_batches(self) -> int:
        return sum(1 for s in self.spans if s[2] == BATCH)

    def busy_s(self) -> Optional[float]:
        """Seconds in which an operation ran, averaged over the chips;
        None where the trace holds no device operation."""
        if not self.ops:
            return None
        return sum(_length(union(iv)) for iv in self.ops.values()) \
            / len(self.ops) / 1e9

    def module_s(self, fragment: str) -> float:
        """Device seconds of the compiled programs whose name holds
        ``fragment`` (e.g. ``jit_greedy_search``), summed over chips."""
        return sum(e - s for iv in self.modules.values()
                   for s, e, n in iv if fragment in n) / 1e9

    def span_s(self, name: str) -> float:
        """Host seconds inside spans called ``name`` (nested calls of
        the same name count once)."""
        return _length(union(s for s in self.spans if s[2] == name)) / 1e9

    def module_s_within(self, fragment: str, span_name: str) -> float:
        """Device seconds of ``fragment`` programs that lie inside host
        spans called ``span_name``."""
        cover = union(s for s in self.spans if s[2] == span_name)
        mods = [(s, e, n) for iv in self.modules.values() for s, e, n in iv
                if fragment in n]
        return _overlap(union(mods), cover) / 1e9

    def top_ops(self, n: int = 10) -> List[List]:
        """The device operations that took most time: [[name, s], ...]."""
        tot: Dict[str, float] = defaultdict(float)
        for iv in self.ops.values():
            for s, e, name in iv:
                tot[name] += (e - s) / 1e9
        return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])
                [:n]]

    def idle_by_host(self, n: int = 10) -> List[List]:
        """Device idle time in the window, by what the host was doing:
        each gap between device operations is named after the innermost
        named host span around its midpoint ("host: other" where none
        is), and the seconds are summed per name. Chip 0 only."""
        if not self.ops:
            return []
        busy = union(self.ops[min(self.ops)])
        gaps, cur = [], self.window[0]
        for s, e in busy:
            if s > cur:
                gaps.append((cur, s))
            cur = max(cur, e)
        if cur < self.window[1]:
            gaps.append((cur, self.window[1]))
        named = sorted((s for s in self.spans if s[2] != WINDOW),
                       key=lambda s: s[0])
        tot: Dict[str, float] = defaultdict(float)
        active: List[Interval] = []
        nxt = 0
        for g0, g1 in gaps:                  # gaps and spans both sorted
            mid = 0.5 * (g0 + g1)
            while nxt < len(named) and named[nxt][0] <= mid:
                active.append(named[nxt])
                nxt += 1
            active = [s for s in active if s[1] >= mid]
            name = min(active, key=lambda s: s[1] - s[0])[2] if active \
                else "host: other"
            tot[name] += (g1 - g0) / 1e9
        return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])
                [:n]]


def union(intervals: Iterable[Interval]) -> List[Tuple[float, float]]:
    """Merged, sorted (start, end) of the intervals."""
    out: List[List[float]] = []
    for s, e, *_ in sorted(intervals, key=lambda x: x[0]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _length(merged: List[Tuple[float, float]]) -> float:
    return sum(e - s for s, e in merged)


def _overlap(a: List[Tuple[float, float]],
             b: List[Tuple[float, float]]) -> float:
    """Length of the intersection of two merged interval lists."""
    i = j = 0
    tot = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            tot += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def _clip(iv: List[Interval], w: Tuple[float, float]) -> List[Interval]:
    return [(max(s, w[0]), min(e, w[1]), n) for s, e, n in iv
            if e > w[0] and s < w[1]]


def _short_names(ops: List[Interval], modules: List[Interval]
                 ) -> List[Interval]:
    """Name each op ``<program>/<instruction>``: the compiled program it
    ran in (``jit_greedy_search``) and the HLO instruction's name, not
    its whole text (``%fusion.79 = pred[16384]... fusion(...)``)."""
    mods = sorted(modules, key=lambda m: m[0])
    starts = [m[0] for m in mods]
    out = []
    for s, e, name in ops:
        op = name.split(" = ", 1)[0]
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < mods[i][1]:
            op = f"{mods[i][2].split('(', 1)[0]}/{op}"
        out.append((s, e, op))
    return out


def find_xplane(log_dir: Path) -> Path:
    found = sorted(Path(log_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load(log_dir: Path, span_names: Set[str]) -> Trace:
    """Read the trace under ``log_dir``; keep host spans whose name is
    in ``span_names`` (plus ``WINDOW`` and ``BATCH``)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(find_xplane(log_dir)))
    wanted = set(span_names) | {WINDOW, BATCH}
    ops: Dict[int, List[Interval]] = {}
    modules: Dict[int, List[Interval]] = {}
    spans: List[Interval] = []
    for plane in pd.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name in ("XLA Ops", "XLA Modules"):
                    dest = ops if line.name == "XLA Ops" else modules
                    dest[dev] = [(e.start_ns, e.end_ns, e.name)
                                 for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.start_ns, e.end_ns, e.name)
                             for e in line.events if e.name in wanted)
    windows = [s for s in spans if s[2] == WINDOW]
    if not windows:
        raise ValueError(f"trace has no {WINDOW!r} span")
    w = (windows[0][0], windows[0][1])
    ops = {d: _short_names(iv, modules.get(d, [])) for d, iv in ops.items()}
    return Trace(window=w,
                 ops={d: _clip(iv, w) for d, iv in ops.items()},
                 modules={d: _clip(iv, w) for d, iv in modules.items()},
                 spans=_clip(spans, w))
