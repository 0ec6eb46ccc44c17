#!/usr/bin/env python3
"""Offer a serve cell's mix at several rates, one set-up for all, to find
the highest rate the engine sustains (the benchmark's own runs never
sweep: the rate is fixed in the traffic file).

    python3 chipbench/sweep.py --workload serve.qwen3-4b.chat \
        --rates 1,2,3,4 --seconds 30 --seed 5

One JSON line per rate: latency quantiles of the requests due in the
window, output tokens completed in the window per second, how long the
drain after the window took, and the engine's step times (all steps,
and those with no prefill chunk). Rates go up in the order given; the
sweep stops after the first rate whose due requests do not all finish
within the drain.
"""
import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from chipbench import harness, traffic  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--page-size", type=int, default=None,
                    help="serve with this KV page size instead of the "
                         "configuration's")
    args = ap.parse_args(argv)
    try:
        run = harness.prepare(harness.ROOT, args.workload, args.seed,
                              args.seconds, False, time.perf_counter())
    except harness.Refused as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 3
    if args.page_size:
        run.config = dict(run.config, serving=dict(
            run.config["serving"], page_size=args.page_size))
    sweep(run, [float(r) for r in args.rates.split(",")])
    return 0


def sweep(run, rates, out=None) -> None:
    from chipbench.drivers import serve
    out = out or sys.stdout
    st = serve.setup(run)
    eng = st["eng"]
    reports = []
    step = eng.step

    def timed(now=None):
        t0 = time.perf_counter()
        rep = step(now)
        reports.append((time.perf_counter() - t0, bool(rep.prefill_shapes),
                        sum(rep.decode_pages)))
        return rep

    eng.step = timed
    print(json.dumps({"setup_s": time.perf_counter() - run.t_start,
                      "serving": run.config["serving"]}),
          file=out, flush=True)
    base = run.traffic
    off = 0
    for rate in rates:
        run.traffic = dict(base, rate_rps=rate)
        reqs = traffic.requests(run.rng("requests"), run.traffic,
                                run.config["vocab_size"], run.seconds,
                                run.seconds + base["drain_max_s"])
        st["reqs"] = [dataclasses.replace(r, rid=r.rid + off) for r in reqs]
        off += len(reqs)
        reports.clear()
        c = serve.window(run, st)
        t = np.array([r[0] for r in reports]) * 1e3
        dec = np.array([r[0] for r in reports if not r[1]]) * 1e3
        print(json.dumps({
            "rate_rps": rate, "due": c["attempted"],
            "unfinished": c["failed"],
            "latency_p50_s": c["latency_p50_s"],
            "latency_p95_s": c["metrics"]["serve_latency_p95_s"],
            "latency_max_s": c["latency_max_s"],
            "tokens_per_s": c["completed_tokens_per_s"],
            "drain_s": c["drain_s"], "late_max_s": c["late_max_s"],
            "steps": len(t), "step_ms_mean": float(t.mean()),
            "decode_step_ms_p50": float(np.median(dec)) if len(dec) else None,
            "prefill_steps": int(sum(r[1] for r in reports)),
            "mean_pages": float(np.mean([r[2] for r in reports])),
            "window_compiles": run.window_compiles,
            "retraces": c["retraces"]}), file=out, flush=True)
        if c["failed"]:
            break
        while eng.has_work:
            eng.step()


if __name__ == "__main__":
    sys.exit(main())
