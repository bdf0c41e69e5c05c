"""From a profiler trace to the numbers the per-layer metrics read.

``load`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into plain
lists of ``[name, start_ns, duration_ns]``: per device plane (``/device:TPU:N``)
its ``XLA Ops`` and ``XLA Modules`` lines, and the host's ``bench.*`` spans
(the harness's ``TraceAnnotation``s) and ``truffle.*`` spans (the program's).
``reduce`` works on those lists alone, so a recorded trace in that form
checks it without a chip.
"""
from __future__ import annotations

import bisect
import glob
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIXES = ("bench.", "truffle.")
WINDOW_SPAN = "bench.traced"
TOP = 10


def load(log_dir: str | Path) -> Dict:
    """The newest ``.xplane.pb`` under ``log_dir`` in plain lists."""
    import jax

    files = sorted(glob.glob(str(Path(log_dir) / "**" / "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = jax.profiler.ProfileData.from_file(files[-1])
    out = {"devices": [], "host": []}
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            dev = {"name": plane.name, "ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key:
                    dev[key] = [[e.name, int(e.start_ns), int(e.duration_ns)]
                                for e in line.events]
            out["devices"].append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"].extend([e.name, int(e.start_ns), int(e.duration_ns)]
                                   for e in line.events
                                   if e.name.startswith(SPAN_PREFIXES))
    return out


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    merged: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return merged


@dataclass
class Summary:
    window_s: float
    busy_s: float                                  # mean over devices
    modules: Dict[str, Tuple[float, int]]          # name -> (device s, calls)
    device_ops: List[Tuple[str, float]] = field(default_factory=list)
    idle_gaps: List[Tuple[str, float]] = field(default_factory=list)
    # host span name -> (seconds inside the window, spans that overlap it)
    spans: Dict[str, Tuple[float, int]] = field(default_factory=dict)

    def module_time(self, prefix: str) -> Tuple[float, int]:
        """Device seconds and calls of the modules whose name starts with
        ``prefix``."""
        s = n = 0
        for name, (t, c) in self.modules.items():
            if name.startswith(prefix):
                s, n = s + t, n + c
        return s, n


def reduce(trace: Dict) -> Summary:
    """Busy time, per-module device time, top ops, idle gaps and the host
    spans' time inside the ``bench.traced`` span."""
    spans = [h for h in trace["host"] if h[0] == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"trace has no {WINDOW_SPAN} span")
    t0 = min(s for _, s, _ in spans)
    t1 = max(s + d for _, s, d in spans)
    devices = [d for d in trace["devices"] if d["ops"]]
    if not devices:
        raise ValueError("trace has no device operations")

    def clip(events):
        for name, s, d in events:
            a, b = max(s, t0), min(s + d, t1)
            if b > a:
                yield name, a, b

    busy, modules, ops = [], defaultdict(lambda: [0.0, 0]), defaultdict(float)
    gaps_by: Dict[str, float] = defaultdict(float)
    host = [(n, s, s + d) for n, s, d in trace["host"] if n != WINDOW_SPAN]
    spans: Dict[str, List] = defaultdict(lambda: [0, 0])
    for name, a, b in clip((n, s, e - s) for n, s, e in host):
        spans[name][0] += b - a
        spans[name][1] += 1
    bounds = sorted({t for _, s, e in host for t in (s, e)})
    for dev in devices:
        merged = _union([(a, b) for _, a, b in clip(dev["ops"])])
        busy.append(sum(b - a for a, b in merged))
        for name, a, b in clip(dev["ops"]):
            ops[name.split(" = ")[0]] += (b - a) / len(devices)   # HLO text -> op
        for name, a, b in clip(dev["modules"]):
            modules[name][0] += (b - a) / len(devices)
            modules[name][1] += 1
        edges = [t0] + [x for ab in merged for x in ab] + [t1]
        gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
        for name, ns in _attribute(host, _split(gaps, bounds)):
            gaps_by[name] += ns / len(devices)
    top_ops = sorted(((n, t / 1e9) for n, t in ops.items()), key=lambda x: -x[1])
    gaps = sorted(((n, t / 1e9) for n, t in gaps_by.items()), key=lambda x: -x[1])
    return Summary(window_s=(t1 - t0) / 1e9, busy_s=sum(busy) / len(busy) / 1e9,
                   modules={n: (t / 1e9, c) for n, (t, c) in modules.items()},
                   device_ops=top_ops[:TOP], idle_gaps=gaps[:TOP],
                   spans={n: (ns / 1e9, k) for n, (ns, k) in spans.items()})


def _split(gaps: List[Tuple[int, int]], bounds: List[int]):
    """Cut each gap at the host spans' starts and ends inside it, so a gap
    that spans several host steps is shared out among them."""
    for a, b in gaps:
        cuts = bounds[bisect.bisect_right(bounds, a):bisect.bisect_left(bounds, b)]
        edges = [a] + cuts + [b]
        yield from zip(edges[:-1], edges[1:])


def _attribute(host: List[Tuple[str, int, int]], gaps: List[Tuple[int, int]]):
    """(innermost ``bench.*`` or ``truffle.*`` span the host was in, length)
    for each piece of idle time, in time order, taken at its midpoint. The
    harness's spans nest, and the program's nest inside them."""
    spans = sorted(host, key=lambda h: (h[1], -h[2]))
    stack: List[Tuple[str, int, int]] = []
    i = 0
    for a, b in gaps:
        t = (a + b) // 2
        while i < len(spans) and spans[i][1] <= t:
            while stack and stack[-1][2] <= spans[i][1]:
                stack.pop()
            stack.append(spans[i])
            i += 1
        while stack and stack[-1][2] <= t:
            stack.pop()
        yield (stack[-1][0] if stack else "outside any bench span"), b - a
