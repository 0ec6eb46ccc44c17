"""Reduce a profiler trace to the numbers the per-layer metrics read.

``jax.profiler`` writes ``<dir>/plugins/profile/<time>/*.xplane.pb``. In it
each TPU is a plane ``/device:TPU:<i>`` whose line ``XLA Ops`` holds one
event per device operation (its name is the HLO text, ``%name = ...``)
and whose line ``XLA Modules`` holds one event per program run, with a
``run_id``. The host plane ``/host:CPU`` holds the harness's spans
(``jax.profiler.TraceAnnotation`` names starting with ``cb:``) on the
main thread, and the runtime's ``DoEnqueueProgram`` events with the same
``run_id``. Times are nanoseconds from the start of the profile.

  busy       union of a device's op intervals inside the traced window
  idle share 1 - busy / window
  op time    summed device durations, by program and op kind
  kernel     summed durations of the custom calls whose name holds the
             kernel's name, and how many there were
  collective summed durations of collective ops, and the part of them
             during which no other op ran on that device
  gaps       the device's idle intervals, each given to the innermost host
             span that covers its middle (device clock aligned to the
             host's by the enqueue of each program run)
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")
HOST_PREFIX = "cb:"
WINDOW_SPAN = "cb:traced"
TOP = 10

Interval = Tuple[float, float]


@dataclass
class Op:
    name: str            # HLO instruction name, e.g. "fusion.12"
    start: float         # ns
    end: float
    custom: bool = False  # a custom call (Pallas kernel)
    module: str = ""     # the program it ran in

    @property
    def kind(self) -> str:
        return re.sub(r"\.\d+$", "", self.name)

    @property
    def collective(self) -> bool:
        k = self.kind
        return any(k.startswith(c) for c in COLLECTIVES)


@dataclass
class Device:
    ops: List[Op] = field(default_factory=list)
    modules: List[Tuple[float, float, str, Optional[int]]] = \
        field(default_factory=list)   # (start, end, name, run_id)


@dataclass
class Trace:
    devices: Dict[str, Device]
    spans: List[Tuple[str, float, float]]   # host spans (cb:*)
    enqueue: Dict[int, float]               # run_id -> host enqueue end


def op_name(text: str) -> Tuple[str, bool]:
    m = re.match(r"\s*%([^\s=]+)\s*=", text)
    name = m.group(1) if m else text.split(" ")[0]
    return name, "tpu_custom_call" in text or "custom-call(" in text


def _stat(ev, key):
    for k, v in ev.stats:
        if k == key:
            return v
    return None


def _module_name(text: str) -> str:
    return re.sub(r"\(\d+\)$", "", text)


def load(path: str) -> Trace:
    """Read one ``.xplane.pb`` into plain tuples."""
    from jax.profiler import ProfileData
    return from_profile(ProfileData.from_file(path))


def from_profile(pd) -> Trace:
    """A ``jax.profiler.ProfileData`` as plain tuples."""
    devices: Dict[str, Device] = {}
    spans: List[Tuple[str, float, float]] = []
    enqueue: Dict[int, float] = {}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = devices.setdefault(plane.name, Device())
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for ev in line.events:
                        name, custom = op_name(ev.name)
                        dev.ops.append(Op(name, ev.start_ns, ev.end_ns,
                                          custom))
                elif line.name == "XLA Modules":
                    for ev in line.events:
                        rid = _stat(ev, "run_id")
                        dev.modules.append((ev.start_ns, ev.end_ns,
                                            _module_name(ev.name),
                                            None if rid is None
                                            else int(rid)))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        spans.append((ev.name, ev.start_ns, ev.end_ns))
                    elif ev.name == "DoEnqueueProgram":
                        rid = _stat(ev, "run_id")
                        if rid is not None:
                            enqueue[int(rid)] = ev.end_ns
    return Trace(devices, spans, enqueue)


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def length(intervals: Sequence[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a: Sequence[Interval], b: Sequence[Interval]
             ) -> List[Interval]:
    """Parts of the (merged) intervals ``a`` not covered by merged ``b``."""
    out: List[Interval] = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def clock_shift(dev: Device, enqueue: Dict[int, float]) -> float:
    """ns to add to a device's times to put them on the host's clock: a
    program cannot start before the host enqueued it."""
    lags = [enqueue[rid] - s for s, _, _, rid in dev.modules
            if rid is not None and rid in enqueue]
    return max(0.0, max(lags)) if lags else 0.0


@dataclass
class Summary:
    window_s: float
    busy_s: float                      # averaged over the devices
    op_s: Dict[str, float]             # "program/op kind" -> s, all devices
    kernels: Dict[str, Tuple[float, int]]  # custom-call name -> (s, calls)
    collective_s: float                # device 0
    collective_exposed_s: float        # device 0, no other op running
    gaps: Dict[str, float]             # host span -> idle s on device 0
    n_devices: int

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def kernel(self, key: str) -> Tuple[float, int]:
        """Device seconds and calls of the custom calls whose name holds
        ``key``, averaged over the devices (0 calls: not in the trace)."""
        secs = sum(s for n, (s, _) in self.kernels.items() if key in n)
        calls = sum(c for n, (_, c) in self.kernels.items() if key in n)
        return secs / self.n_devices, calls // self.n_devices

    def breakdown(self) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:TOP]
        gaps = sorted(self.gaps.items(), key=lambda kv: -kv[1])[:TOP]
        return {"device_ops": [[k, v / self.n_devices] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}


def summarize(trace: Trace, n_devices: Optional[int] = None) -> Summary:
    """Reduce a trace over the host span ``cb:traced``."""
    win = [(s, e) for n, s, e in trace.spans if n == WINDOW_SPAN]
    if not win:
        raise ValueError(f"no {WINDOW_SPAN} span in the trace")
    lo, hi = win[0]
    names = sorted(trace.devices, key=lambda n: int(n.rsplit(":", 1)[1]))
    if n_devices is not None:
        names = names[:n_devices]
    if not names:
        raise ValueError("no TPU device plane in the trace")
    busy = 0.0
    op_s: Dict[str, float] = {}
    kernels: Dict[str, Tuple[float, int]] = {}
    coll_s = exposed = 0.0
    gaps: Dict[str, float] = {}
    for i, name in enumerate(names):
        dev = trace.devices[name]
        shift = clock_shift(dev, trace.enqueue)
        mods = sorted(dev.modules)
        starts = [m[0] for m in mods]
        ops = [Op(o.name, o.start + shift, o.end + shift, o.custom,
                  _enclosing(mods, starts, o.start))
               for o in dev.ops]
        ops = [o for o in ops if o.end > lo and o.start < hi]
        merged = union(clip([(o.start, o.end) for o in ops], lo, hi))
        busy += length(merged)
        for o in ops:
            d = min(o.end, hi) - max(o.start, lo)
            key = f"{o.module}/{o.kind}" if o.module else o.kind
            op_s[key] = op_s.get(key, 0.0) + d / 1e9
            if o.custom:
                s, c = kernels.get(o.name, (0.0, 0))
                kernels[o.name] = (s + d / 1e9, c + 1)
        if i == 0:
            coll = union(clip([(o.start, o.end) for o in ops
                               if o.collective], lo, hi))
            other = union(clip([(o.start, o.end) for o in ops
                                if not o.collective], lo, hi))
            coll_s = length(coll) / 1e9
            exposed = length(subtract(coll, other)) / 1e9
            gaps = _gaps_by_span(subtract([(lo, hi)], merged), trace.spans)
    return Summary(window_s=(hi - lo) / 1e9,
                   busy_s=busy / len(names) / 1e9, op_s=op_s,
                   kernels=kernels, collective_s=coll_s,
                   collective_exposed_s=exposed, gaps=gaps,
                   n_devices=len(names))


def _enclosing(mods, starts, t: float) -> str:
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and mods[i][0] <= t <= mods[i][1]:
        return mods[i][2]
    return ""


def _gaps_by_span(gaps: Sequence[Interval],
                  spans: Sequence[Tuple[str, float, float]]
                  ) -> Dict[str, float]:
    """Idle seconds by the innermost host span covering each gap's middle
    (``(no span)`` where none does). Spans of one thread nest, so the
    innermost is the latest-starting one that still covers the middle."""
    inner = sorted((s, e, n) for n, s, e in spans if n != WINDOW_SPAN)
    starts = [s for s, _, _ in inner]
    out: Dict[str, float] = {}
    for s, e in gaps:
        mid = (s + e) / 2
        key = "(no span)"
        for j in range(bisect.bisect_right(starts, mid) - 1, -1, -1):
            if inner[j][1] >= mid:
                key = inner[j][2]
                break
        out[key] = out.get(key, 0.0) + (e - s) / 1e9
    return out


def summarize_dir(trace_dir: str, n_devices: Optional[int] = None) -> Summary:
    """Summarize the newest ``.xplane.pb`` under ``trace_dir``."""
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return summarize(load(files[-1]), n_devices)
