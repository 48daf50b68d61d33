#!/usr/bin/env python3
"""Run one cell traced, and put the device's idle time down to host stages.

    python3 chipbench/stage_report.py --workload <name> --seed <n> --seconds <s> [--save PATH]

It makes the same run as ``run.py --trace 1`` and prints the same result
line, with each idle gap of the breakdown labelled by the innermost program
spans (``repro.obs``, ``zllm.*``) open at its middle. A last line follows,
the stage report: the share of the device's idle time in the window that
lies inside at least one program span, the labelled gaps, and the ingest
job worker's time split by stage per upload (``bench/stages.py``).
``--save`` also writes the reduced trace with the program spans as JSON.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run as bench_run  # noqa: E402
from bench import stages, trace  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--save", default=None, metavar="PATH")
    args = ap.parse_args(argv)

    got = {}
    read_xplane, plain_breakdown = trace.read_xplane, bench_run.breakdown

    def read_with_spans(path):
        got["spans"] = stages.read_program_spans(path)
        return dict(read_xplane(path), program_spans=got["spans"])

    def breakdown(run):
        got["run"] = run
        out = plain_breakdown(run)
        out["idle_gaps"] = stages.label_gaps(run, got.get("spans", []))
        return out

    # run.main looks both up when it calls them; the run itself is its own
    trace.read_xplane, bench_run.breakdown = read_with_spans, breakdown
    cmd = ["--workload", args.workload, "--seed", str(args.seed),
           "--seconds", str(args.seconds), "--trace", "1"]
    if args.save:
        cmd += ["--save-trace", args.save]
    rc = bench_run.main(cmd)
    if rc != 0 or "run" not in got:
        return rc or 1
    run, spans = got["run"], got["spans"]
    report = {
        "idle_covered_share": stages.idle_covered_share(run.trace, spans),
        "idle_gaps": stages.label_gaps(run, spans),
        "per_upload": stages.job_decomposition(spans, run.trace.lo, run.trace.hi),
        "program_spans": len(spans),
    }
    print(json.dumps(report), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
