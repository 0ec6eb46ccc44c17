"""The program's own spans and named scopes in a profiler trace.

The program marks its layers with host spans named ``mlitb.*``
(``repro.core.obs``), whose stats carry its counters, and names the ops of
its reducer programs with ``jax.named_scope`` paths (``reduce/pack`` ...).
This module reads them from the newest ``.xplane.pb`` of a traced run, on
the clocks ``trace.py`` uses:

  spans        every ``mlitb.*`` host span with its args, and the
               harness's ``cb:*`` spans
  gaps         device 0's idle intervals inside ``cb:traced``, each given
               to the innermost span of either kind that covers its middle
               (device clock moved to the host's by ``trace.clock_shift``)
  op time      device op seconds inside ``cb:traced`` by program and named
               scope; an op's scope path is the ``tf_op`` stat of its event
               or of its event metadata (which ``ProfileData`` does not
               show, so it is read from the protobuf itself), else the
               ``op_name`` of the metadata in its HLO text, e.g.
               ``jit(reduce_blocktopk_step)/reduce/pack/concatenate``
  program time device seconds of each program's runs inside ``cb:traced``

A trace of a program without spans or scopes reads as empty, and the
metrics that read it report nothing.

    python3 -m chipbench.spans <trace_dir>

prints the idle gaps by span, the program spans' counts and mean lengths,
and the device time by scope.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
import statistics
import sys
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
from chipbench import trace as tr  # noqa: E402

PREFIX = "mlitb."


@dataclass
class Span:
    name: str
    start: float            # ns, host clock
    end: float
    args: Dict[str, Any] = field(default_factory=dict)

    @property
    def ms(self) -> float:
        return (self.end - self.start) / 1e6


@dataclass
class Reading:
    window: Tuple[float, float]          # cb:traced, ns
    spans: List[Span]                    # mlitb.* inside the window
    gaps: Dict[str, float]               # span name -> idle s, device 0
    scope_s: Dict[Tuple[str, str], float]  # (program, scope path) -> s
    program_s: Dict[str, float]          # program -> s of its runs

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def named(self, name: str) -> List[Span]:
        """The spans ``mlitb.<name>`` inside the window, in order."""
        return [s for s in self.spans if s.name == PREFIX + name]

    def arg_sum(self, name: str, key: str) -> Optional[float]:
        """The sum of arg ``key`` over the spans ``mlitb.<name>`` that
        carry it (None when none does)."""
        vals = [s.args[key] for s in self.named(name) if key in s.args]
        return float(sum(float(v) for v in vals)) if vals else None

    def median_ms(self, name: str) -> Optional[float]:
        spans = self.named(name)
        return statistics.median(s.ms for s in spans) if spans else None

    def gap_share(self, names: Sequence[str]) -> Optional[float]:
        """Percent of the window in which the device idled with one of
        ``mlitb.<name>`` the innermost span (None when no such span is
        in the window)."""
        if not any(self.named(n) for n in names):
            return None
        idle = sum(self.gaps.get(PREFIX + n, 0.0) for n in names)
        return 100.0 * idle / self.window_s

    def scope_share(self, program: str, scopes: Sequence[str]
                    ) -> Optional[float]:
        """Percent of the device time of the programs whose name starts
        with ``program`` spent in ops under one of ``scopes`` (None when
        no op of those programs carries a scope path of its family, the
        first component of ``scopes[0]``)."""
        total = sum(s for p, s in self.program_s.items()
                    if p.startswith(program))
        family = scopes[0].split("/")[0]
        mine = {k: s for k, s in self.scope_s.items()
                if k[0].startswith(program) and under(k[1], family)}
        if not total or not mine:
            return None
        part = sum(s for (_, path), s in mine.items()
                   if any(under(path, sc) for sc in scopes))
        return 100.0 * part / total


def under(path: str, scope: str) -> bool:
    """Whether the scope path ``path`` runs through ``scope`` (whole
    components: ``jit(f)/reduce/pack/mul`` is under ``reduce/pack``)."""
    return f"/{scope}/" in f"/{path}/"


_OP_NAME = re.compile(r'op_name="([^"]*)"')


def op_scope(text: str, stats, meta: Optional[str] = None) -> str:
    """An op's scope path: its ``tf_op`` stat, else ``meta`` (the
    ``tf_op`` of its event metadata), each less a TensorFlow-style
    ``:type`` suffix, else the ``op_name`` in the HLO text's metadata."""
    for k, v in stats:
        if k == "tf_op" and v:
            meta = str(v)
    if meta:
        return meta.split(":")[0]
    m = _OP_NAME.search(text)
    return m.group(1) if m else ""


def _varint(b, i: int) -> Tuple[int, int]:
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        if c < 0x80:
            return x, i
        shift += 7


def _fields(b):
    """(field number, value) of each field of a protobuf message: an int
    for a varint, a ``memoryview`` slice for anything else."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            ln, i = _varint(b, i)
            v, i = b[i:i + ln], i + ln
        elif wire in (1, 5):
            ln = 8 if wire == 1 else 4
            v, i = b[i:i + ln], i + ln
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield key >> 3, v


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def metadata_stat(raw: bytes, stat: str = "tf_op"
                  ) -> Dict[str, Dict[str, str]]:
    """Device plane name -> {event metadata name: its stat ``stat``},
    read from a serialized ``XSpace`` (planes 1; a plane's name 2, event
    metadata map 4, stat metadata map 5; an event metadata's name 2 and
    stats 5; a stat's metadata id 1, string 5 or reference 7 to the stat
    metadata that names the value)."""
    out: Dict[str, Dict[str, str]] = {}
    for f, plane in _fields(memoryview(raw)):
        if f != 1:
            continue
        name, events, names = "", [], {}
        for pf, v in _fields(plane):
            if pf == 2:
                name = _text(v)
            elif pf == 4:
                events.append(v)
            elif pf == 5:
                entry = dict(_fields(v))
                md = dict(_fields(entry.get(2, b"")))
                names[md.get(1, entry.get(1, 0))] = _text(md.get(2, b""))
        if not name.startswith("/device:"):
            continue
        want = [i for i, n in names.items() if n == stat]
        found = out.setdefault(name, {})
        for entry in events:
            md = {}
            for mf, mv in _fields(dict(_fields(entry)).get(2, b"")):
                if mf == 5:
                    st = dict(_fields(mv))
                    if st.get(1) in want:
                        md["value"] = _text(st[5]) if 5 in st \
                            else names.get(st.get(7), "")
                elif mf == 2:
                    md["name"] = _text(mv)
            if md.get("value"):
                found[md.get("name", "")] = md["value"]
    return out


def _stats(ev) -> Dict[str, Any]:
    return dict(ev.stats)


def from_xspace(raw: bytes) -> Reading:
    """Read a serialized ``XSpace`` (an ``.xplane.pb``)."""
    from jax.profiler import ProfileData
    return from_profile(ProfileData.from_serialized_xspace(raw),
                        metadata_stat(raw))


def from_profile(pd, meta: Optional[Dict[str, Dict[str, str]]] = None
                 ) -> Reading:
    """Read a ``jax.profiler.ProfileData``, with ``meta`` the ``tf_op``
    of each device plane's event metadata (``metadata_stat``); raises
    ``ValueError`` without the window span ``cb:traced``."""
    meta = meta or {}
    base = tr.from_profile(pd)          # device ops and runs, cb:* spans
    spans = [Span(n, s, e) for n, s, e in base.spans]
    scopes: Dict[str, List[str]] = {}   # device -> scope path of each op
    for plane in pd.planes:
        if plane.name in base.devices:
            tf_op = meta.get(plane.name, {})
            scopes[plane.name] = [
                op_scope(ev.name, ev.stats, tf_op.get(ev.name))
                for line in plane.lines if line.name == "XLA Ops"
                for ev in line.events]
        elif plane.name == "/host:CPU":
            spans += [Span(ev.name, ev.start_ns, ev.end_ns, _stats(ev))
                      for line in plane.lines for ev in line.events
                      if ev.name.startswith(PREFIX)]
    win = [s for s in spans if s.name == tr.WINDOW_SPAN]
    if not win:
        raise ValueError(f"no {tr.WINDOW_SPAN} span in the trace")
    lo, hi = win[0].start, win[0].end
    names = sorted(base.devices, key=lambda n: int(n.rsplit(":", 1)[1]))
    gaps: Dict[str, float] = {}
    scope_s: Dict[Tuple[str, str], float] = {}
    program_s: Dict[str, float] = {}
    for i, name in enumerate(names):
        dev = base.devices[name]
        shift = tr.clock_shift(dev, base.enqueue)
        mods = sorted((s + shift, e + shift, m) for s, e, m, _ in dev.modules)
        starts = [m[0] for m in mods]
        for s, e, m in mods:
            if e > lo and s < hi:
                program_s[m] = program_s.get(m, 0.0) + \
                    (min(e, hi) - max(s, lo)) / 1e9
        busy = []
        for op, path in zip(dev.ops, scopes[name]):
            s, e = op.start + shift, op.end + shift
            if e <= lo or s >= hi:
                continue
            busy.append((s, e))
            key = (tr._enclosing(mods, starts, s), path)
            scope_s[key] = scope_s.get(key, 0.0) + \
                (min(e, hi) - max(s, lo)) / 1e9
        if i == 0:
            idle = tr.subtract([(lo, hi)], tr.union(tr.clip(busy, lo, hi)))
            gaps = innermost(idle, [s for s in spans
                                    if s.name != tr.WINDOW_SPAN])
    inside = [s for s in spans if s.name.startswith(PREFIX)
              and s.start >= lo and s.end <= hi]
    return Reading((lo, hi), inside, gaps, scope_s, program_s)


def innermost(gaps: Sequence[tr.Interval], spans: Sequence[Span]
              ) -> Dict[str, float]:
    """Idle seconds by the innermost span covering each gap's middle
    (``(no span)`` where none does). Spans of one thread nest, so the
    innermost is the latest-starting one that still covers the middle
    (the rule of ``trace.py``, over spans of either kind; of two that
    start together, the shorter)."""
    inner = sorted((s.start, -s.end, s.name) for s in spans)
    inner = [(s, -e, n) for s, e, n in inner]
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


def newest(trace_dir: str) -> Optional[str]:
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    return files[-1] if files else None


_LOADED: Dict[Tuple[str, float], Reading] = {}


def load(trace_dir: str) -> Optional[Reading]:
    """The reading of the newest ``.xplane.pb`` under ``trace_dir``, read
    once per process (the readers of one run share it); None when there
    is no trace."""
    path = newest(trace_dir)
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _LOADED:
        _LOADED.clear()
        with open(path, "rb") as f:
            _LOADED[key] = from_xspace(f.read())
    return _LOADED[key]


def read(ctx) -> Optional[Reading]:
    """The reading of a metric's run (``ctx.run.trace_dir``)."""
    return load(ctx.run.trace_dir)


def report(r: Reading, out=None) -> None:
    """The gap table by span, the program spans and the scope times."""
    out = out or sys.stdout
    print(f"window {r.window_s:.6f} s", file=out)
    print("| span | idle s | idle % of window |", file=out)
    print("| --- | --- | --- |", file=out)
    for name, s in sorted(r.gaps.items(), key=lambda kv: -kv[1]):
        print(f"| `{name}` | {s:.6f} | {100 * s / r.window_s:.3f} |",
              file=out)
    print("| span | count | mean ms | median ms |", file=out)
    print("| --- | --- | --- | --- |", file=out)
    for name in sorted({s.name for s in r.spans}):
        ms = [s.ms for s in r.spans if s.name == name]
        print(f"| `{name}` | {len(ms)} | {statistics.mean(ms):.4f} | "
              f"{statistics.median(ms):.4f} |", file=out)
    print("| program | scope | device s |", file=out)
    print("| --- | --- | --- |", file=out)
    for (prog, path), s in sorted(r.scope_s.items(), key=lambda kv: -kv[1]
                                  )[:40]:
        print(f"| `{prog}` | `{path}` | {s:.6f} |", file=out)


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print("usage: python3 -m chipbench.spans <trace_dir>",
              file=sys.stderr)
        return 2
    r = load(argv[0])
    if r is None:
        print(f"no .xplane.pb under {argv[0]}", file=sys.stderr)
        return 1
    report(r)
    return 0


if __name__ == "__main__":
    sys.exit(main())
