"""One run of one cell: set-up, warm-up, the measured window, the
comparison that decides ``correct``, and the result line.

The path driver named by the cell's traffic file (``drivers/<name>.py``)
supplies four functions:

  setup(run) -> state         build the system under test, drive it
                              through its first steps, warm every shape
  window(run, state) -> dict  measure for ``run.seconds`` between
                              ``run.open_window()`` and ``run.close_window()``;
                              returns counters, with the cell's end-to-end
                              values under ``"metrics"``
  release(run, state)         free the program's device state, keeping
                              what the comparison needs
  check(run, state, counters) -> [(name, value, limit), ...]

A run is correct when every compared value is at or under its limit.
"""
from __future__ import annotations

import argparse
import gc
import importlib
import importlib.util
import json
import math
import os
import shutil
import sys
import time
import zlib
from types import SimpleNamespace
from typing import Dict, List, Optional

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "chipbench")

# the part of the window a traced run profiles: it starts this far in and
# lasts at most TRACE_MAX_S (and never past the window's end)
TRACE_FROM = 0.3
TRACE_MAX_S = 4.0


class Refused(Exception):
    """The run cannot take place here: no chip, too few chips, no program."""


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


# ---------------------------------------------------------------------------
# compile accounting (jax.monitoring): set-up compiles, the window must not
# ---------------------------------------------------------------------------
COMPILES = {"n": 0, "secs": 0.0, "cache_hits": 0, "installed": False}


def _on_duration(event: str, secs: float, **_) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        COMPILES["n"] += 1
        COMPILES["secs"] += secs


def _on_event(event: str, **_) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        COMPILES["cache_hits"] += 1


def install_compile_listeners() -> None:
    import jax
    if not COMPILES["installed"]:
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        jax.monitoring.register_event_listener(_on_event)
        COMPILES["installed"] = True


# ---------------------------------------------------------------------------
def cell_spec(spec: dict, name: str):
    """(cell, config entry, end-to-end metrics, per-layer metrics) of the
    cell ``name``: a metric with a ``workloads`` key belongs to the cells it
    lists; a per-layer metric without one to every cell reporting the
    end-to-end metric it moves."""
    cells = {c["name"]: c for c in spec["workloads"]}
    if name not in cells:
        raise Refused(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    e2e = [m for m in spec["end_to_end"]
           if name in m.get("workloads", [name])]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in moved)]
    return cell, conf, e2e, per_layer


class Run:
    """Everything one run knows: the cell's files, the seed, the devices,
    the clock of the window and the profiler schedule."""

    def __init__(self, *, root: str, cell: dict, config: dict, traffic: dict,
                 limits: dict, e2e: List[dict], per_layer: List[dict],
                 seed: int, seconds: float, trace: bool, devices: list,
                 t_start: float):
        self.root = root
        self.cell = cell
        self.name = cell["name"]
        self.chips = int(cell["chips"])
        self.config = config
        self.traffic = traffic
        self.limits = limits
        self.e2e = e2e
        self.per_layer = per_layer
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.devices = devices
        self.t_start = t_start
        self.trace_dir = os.path.join(root, ".chipbench", "trace")
        self.t0: Optional[float] = None
        self.window_s: Optional[float] = None
        self.setup_s: Optional[float] = None
        self.traced_ns: List[int] = []
        self._trace_on = self._trace_off = None
        self._annotation = None
        self._compiles0 = 0
        self.window_compiles = 0

    # -- seeds ----------------------------------------------------------
    def seedseq(self, stream: str) -> np.random.SeedSequence:
        """One independent stream per use, from ``--seed`` (any integer)."""
        return np.random.SeedSequence(
            entropy=self.seed % (1 << 64),
            spawn_key=(zlib.crc32(stream.encode()),))

    def rng(self, stream: str) -> np.random.Generator:
        return np.random.default_rng(self.seedseq(stream))

    def key(self, stream: str):
        import jax
        word = int(self.seedseq(stream).generate_state(1, np.uint32)[0])
        return jax.random.PRNGKey(word & 0x7FFFFFFF)

    # -- spans ----------------------------------------------------------
    @staticmethod
    def span(name: str):
        """A host span in the profiler's trace (inert when it is off)."""
        import jax
        return jax.profiler.TraceAnnotation(name)

    # -- the window -----------------------------------------------------
    def open_window(self) -> None:
        self.t0 = time.perf_counter()
        self.setup_s = self.t0 - self.t_start
        self._compiles0 = COMPILES["n"]
        if self.trace:
            on = TRACE_FROM * self.seconds
            self._trace_on = on
            self._trace_off = min(self.seconds, on + TRACE_MAX_S)
            shutil.rmtree(self.trace_dir, ignore_errors=True)

    def elapsed(self) -> float:
        return time.perf_counter() - self.t0

    def tick(self) -> float:
        """Called once per loop iteration: starts and stops the profiler
        on schedule. Returns the seconds since the window opened."""
        now = self.elapsed()
        if self._trace_on is not None and now >= self._trace_on:
            self._trace_on = None
            self._start_trace()
        elif (self._trace_on is None and self._trace_off is not None
              and now >= self._trace_off):
            self._trace_off = None
            self._stop_trace()
        return now

    def window_open(self) -> bool:
        return self.tick() < self.seconds

    def close_window(self) -> float:
        """The measured window ends here (after its last work completed)."""
        self.window_s = self.elapsed()
        if self._annotation is not None:
            self._trace_off = None
            self._stop_trace()
        self.seal()
        return self.window_s

    def seal(self) -> None:
        """Count the compiles since the window opened (a driver whose
        window is followed by a drain calls it again when the drain ends)."""
        self.window_compiles = COMPILES["n"] - self._compiles0

    def _start_trace(self) -> None:
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0     # host spans only, no Python calls
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self._annotation = jax.profiler.TraceAnnotation("cb:traced")
        self._annotation.__enter__()
        self.traced_ns = [time.time_ns()]

    def _stop_trace(self) -> None:
        import jax
        self.traced_ns.append(time.time_ns())
        self._annotation.__exit__(None, None, None)
        self._annotation = None
        jax.profiler.stop_trace()


# ---------------------------------------------------------------------------
def peaks_for(kind: str, root: str = ROOT) -> dict:
    table = load_json(os.path.join(root, "chipbench", "peaks.json"))
    if kind not in table["devices"]:
        raise Refused(f"device kind {kind!r} is not in chipbench/peaks.json")
    return table["devices"][kind]


def load_reader(name: str, root: str = ROOT):
    path = os.path.join(root, "chipbench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def device_info(run: Run) -> dict:
    d = run.devices[0]
    peaks = [(x.memory_stats() or {}).get("peak_bytes_in_use")
             for x in run.devices]
    peaks = [p for p in peaks if p is not None]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(run.devices),
            "memory_peak_bytes": int(max(peaks)) if peaks else None}


def execute(run: Run, *, out=None, err=None) -> dict:
    """Run the cell once and print its result line; returns the line."""
    import jax
    out = out or sys.stdout
    err = err or sys.stderr
    driver = importlib.import_module(
        f"chipbench.drivers.{run.traffic['driver']}")
    state = driver.setup(run)
    counters = driver.window(run, state)
    device = device_info(run)
    driver.release(run, state)
    jax.clear_caches()          # drop executables that pin program state
    gc.collect()
    held = [(d.memory_stats() or {}).get("bytes_in_use")
            for d in run.devices]
    compared = driver.check(run, state, counters)

    metrics: Dict[str, dict] = {}
    breakdown = None
    if run.trace:
        from chipbench import trace as tr
        summary = tr.summarize_dir(run.trace_dir,
                                   n_devices=len(run.devices))
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        breakdown = summary.breakdown()
        print(json.dumps({"kernels": summary.kernels,
                          "collective_s": summary.collective_s}), file=err)
        ctx = SimpleNamespace(run=run, counters=counters, trace=summary,
                              peaks=peaks_for(device["kind"], run.root))
        for m in run.per_layer:
            value = load_reader(m["name"], run.root).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
    else:
        values = dict(counters["metrics"], setup_s=run.setup_s)
        for m in run.e2e:
            metrics[m["name"]] = {"value": float(values[m["name"]]),
                                  "unit": m["unit"]}

    correct = all(v <= lim for _, v, lim in compared)
    line = {"correct": bool(correct),
            "attempted": int(counters["attempted"]),
            "failed": int(counters["failed"]),
            "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["window_compiles"] = run.window_compiles
    line["compared"] = {n: {"value": float(v), "limit": float(lim)}
                        for n, v, lim in compared}
    print(json.dumps({"setup_s": run.setup_s, "window_s": run.window_s,
                      "bytes_held_after_release": held,
                      "window_compiles": run.window_compiles,
                      "compile_s": COMPILES["secs"],
                      "compiles": COMPILES["n"],
                      "cache_hits": COMPILES["cache_hits"],
                      "counters": {k: v for k, v in counters.items()
                                   if isinstance(v, (int, float, str))}}),
          file=err)
    for n, v, lim in compared:
        print(f"compared {n}: {v!r} limit {lim!r} "
              f"{'ok' if v <= lim else 'FAILED'}", file=err)
    err.flush()
    print(json.dumps(line), file=out, flush=True)
    return line


def prepare(root: str, workload: str, seed: int, seconds: float,
            trace: bool, t_start: float) -> Run:
    """Read the cell's files and take its chips; raises ``Refused`` when
    the run cannot take place here."""
    bench = os.path.join(root, "BENCHMARK.json")
    if not os.path.exists(bench):
        raise Refused(f"no BENCHMARK.json under {root}")
    src = os.path.join(root, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise Refused(f"no program (src/repro) under {root}")
    spec = load_json(bench)
    cell, conf, e2e, per_layer = cell_spec(spec, workload)
    config = load_json(os.path.join(root, conf["file"]))
    traffic = load_json(os.path.join(root, "chipbench", "traffic",
                                     cell["traffic"] + ".json"))
    limits = load_json(os.path.join(root, "chipbench", "limits",
                                    workload + ".json"))
    if src not in sys.path:
        sys.path.insert(0, src)
    # the persistent compile cache: where the environment puts it, else
    # inside the checkout at a fixed path, so that only the first run of
    # a cell there compiles; the TPU runtime's logs are off (they would go
    # to a fixed path under /tmp)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(root, ".jax_cache"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise Refused(f"needs a TPU, JAX found {devices[0].platform}")
    if len(devices) < int(cell["chips"]):
        raise Refused(f"cell {workload} needs {cell['chips']} chips, "
                      f"JAX found {len(devices)}")
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    install_compile_listeners()
    return Run(root=root, cell=cell, config=config, traffic=traffic,
               limits=limits, e2e=e2e, per_layer=per_layer, seed=seed,
               seconds=seconds, trace=trace,
               devices=devices[:int(cell["chips"])], t_start=t_start)


def main(argv=None, t_start: Optional[float] = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (args.seconds > 0 and math.isfinite(args.seconds)):
        print("chipbench: --seconds must be positive", file=sys.stderr)
        return 2
    try:
        run = prepare(ROOT, args.workload, args.seed, args.seconds,
                      bool(args.trace), t_start)
    except Refused as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    execute(run)
    return 0
