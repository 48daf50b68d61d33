#!/usr/bin/env python3
"""Run one cell of the on-chip benchmark once.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic mix are found by name from
``BENCHMARK.json``. With ``--trace 0`` the last line of standard output
holds the cell's end-to-end metrics; with ``--trace 1`` the window is traced
and the line holds its per-layer metrics. Either way every answer of the
window is compared with the generator's bytes, and the numbers compared are
printed beside their limits, last on standard error and last in the line.

It exits non-zero and prints no result where JAX finds no TPU, or fewer
chips than the cell asks for. ``--control lossy`` runs the store with the
low bit of every XOR delta dropped, a lossy codec: its run must come out
not correct (the benchmark's own runs never set it).
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
WORK = os.path.join(HERE, "work")
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(REPO, "src"))


def log(msg: str) -> None:
    print(f"chipbench: {msg}", file=sys.stderr, flush=True)


def breakdown(run) -> dict:
    """Top device operations, and the longest idle gaps labelled by what
    the clients had in flight at their middle."""
    tr = run.trace
    gaps = []
    for a, b in tr.idle_gaps()[:10]:
        mid = run.t_span + ((a + b) / 2 - tr.lo) / 1e9
        busy = [r for r in run.records if r["t_send"] <= mid < r["t_ack"]]
        gaps.append([f"{len(busy)} PUT in flight", (b - a) / 1e9])
    ops = [[n.split(" = ")[0], s] for n, s in tr.top_ops(10)]
    return {"device_ops": ops, "idle_gaps": gaps}


def result(run, metrics: dict, device: dict, traced: bool) -> dict:
    from bench.harness import correct
    checks = run.checks
    out = {"correct": correct(checks), "attempted": len(run.records),
           "failed": checks["failed_requests"]["value"],
           "metrics": metrics, "device": device}
    if traced:
        out["breakdown"] = breakdown(run)
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", choices=("lossy",), default=None)
    ap.add_argument("--save-trace", default=None, metavar="PATH",
                    help="also write the reduced trace (device ops, spans) and the "
                         "window's upload records as JSON")
    args = ap.parse_args(argv)

    from bench import harness, registry
    from bench.trace import Reduced, find_xplane, read_xplane

    bm = registry.load_benchmark()
    cell = registry.cell(bm, args.workload)
    config = registry.config(bm, cell["config"])
    traffic = registry.traffic(cell["traffic"])

    from repro.compile_cache import configure_compile_cache
    cache = configure_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        log(f"needs {cell['chips']} TPU chip(s); JAX found "
            f"{len(devices)} {devices[0].platform} device(s)")
        return 2
    peaks = registry.peaks(devices[0].device_kind)
    log(f"{args.workload} seed {args.seed} on {len(devices)} x "
        f"{devices[0].device_kind}, compile cache {cache}")

    trace_dir = os.path.join(WORK, "trace") if args.trace else None
    if trace_dir:
        shutil.rmtree(trace_dir, ignore_errors=True)
    try:
        run = harness.run_cell(
            cell, config, traffic, args.seed, args.seconds,
            os.path.join(WORK, "run"), trace_dir=trace_dir, log=log,
            patch=harness.lossy_control if args.control else None, t_start=T_START)
        run.peaks = peaks
        device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
                  "count": jax.device_count(),
                  "memory_peak_bytes": run.notes.get("memory_peak_bytes")}
        if trace_dir:
            path = find_xplane(trace_dir)
            if path is None:
                raise RuntimeError("the profiler wrote no trace")
            data = read_xplane(path)
            if args.save_trace:
                with open(args.save_trace, "w") as f:
                    json.dump(dict(data, records=run.records), f)
            run.trace = Reduced(data)
            device["busy_s"] = run.trace.busy_s()
            device["window_s"] = run.trace.window_s
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    metrics = {}
    for m in registry.metrics_for(bm, args.workload, bool(args.trace)):
        value = registry.reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    log(f"notes {json.dumps(run.notes)}")
    out = result(run, metrics, device, bool(args.trace))
    for name, c in out["checks"].items():
        log(f"check {name} = {c['value']} (limit {c['rule']} {c['limit']})")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
