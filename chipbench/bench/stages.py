"""The program's own stage spans and counters (``repro.obs``), read two ways.

- Counters: ``/stats`` ``server.stages`` gives, per span name, the count,
  seconds (summed over threads) and bytes of every stage the server process
  ran. ``seconds_per_gb`` turns the window's change of them into seconds per
  GB of user bytes taken in, the unit of the ``*_s_per_GB.ingest`` metrics.
- Spans: every ``zllm.`` event of the host planes of the profiler's trace,
  as ``[name, start_ns, dur_ns, line, stats]`` on the device ops' clock
  (``read_program_spans``). The reductions below put each device-idle gap
  down to the spans open in it, and split the ingest job worker's time
  into the stages it ran; they work on those lists alone.

A program without the counters or spans gives None or empty lists here,
never an error.
"""

from __future__ import annotations

from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from bench.readers import delta
from bench.trace import DEVICE_PLANE, merged

PROGRAM_PREFIX = "zllm."
JOB_SPAN = "zllm.job"

Span = Tuple[str, float, float, str, Dict]  # name, start_ns, dur_ns, line, stats


def seconds_per_gb(run, *names: str) -> Optional[float]:
    """Seconds the named stages took in the window per GB (1e9 B) of user
    bytes the store took in: the change of ``server.stages.<name>.seconds``
    over the change of ``store.raw_bytes``, between the window's two
    ``/stats`` reads. None where the server counts no stages or the store
    took in no byte."""
    before = run.stats0.get("server", {}).get("stages")
    after = run.stats1.get("server", {}).get("stages")
    raw = delta(run, "store", "raw_bytes")
    if after is None or raw <= 0:
        return None
    before = before or {}
    secs = sum(after.get(n, {}).get("seconds", 0.0)
               - before.get(n, {}).get("seconds", 0.0) for n in names)
    return secs / (raw / 1e9)


def read_program_spans(path: str) -> List[list]:
    """Every ``zllm.`` event on the trace's host planes, in start order.
    ``line`` names the thread: ``<plane>/<line index>:<line name>``."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if DEVICE_PLANE.match(plane.name):
            continue
        for i, line in enumerate(plane.lines):
            where = f"{plane.name}/{i}:{line.name}"
            for e in line.events:
                if e.name.startswith(PROGRAM_PREFIX):
                    out.append([e.name, e.start_ns, e.duration_ns, where,
                                dict(e.stats)])
    return sorted(out, key=lambda s: s[1])


def open_at(spans: Iterable[Span], t: float) -> List[str]:
    """Names of the innermost spans open at ``t``, one per thread, sorted."""
    inner: Dict[str, Span] = {}
    for s in spans:
        if s[1] <= t < s[1] + s[2]:
            cur = inner.get(s[3])
            if cur is None or (s[1], -s[2]) > (cur[1], -cur[2]):
                inner[s[3]] = s
    return sorted({s[0] for s in inner.values()})


def label_gaps(run, spans: Sequence[Span]) -> List[list]:
    """The ten longest device-idle gaps of a traced run as ``[label,
    seconds]``: what the clients had in flight at the gap's middle, then
    the innermost program spans open there, e.g. ``"2 PUT in flight:
    zllm.entropy, zllm.hash.tensor"``."""
    tr = run.trace
    out = []
    for a, b in tr.idle_gaps()[:10]:
        mid_ns = (a + b) / 2
        mid = run.t_span + (mid_ns - tr.lo) / 1e9
        busy = sum(1 for r in run.records if r["t_send"] <= mid < r["t_ack"])
        label = f"{busy} PUT in flight"
        names = open_at(spans, mid_ns)
        out.append([label + (": " + ", ".join(names) if names else ""),
                    (b - a) / 1e9])
    return out


def _union_ns(intervals: Iterable[Tuple[float, float]], lo: float,
              hi: float) -> List[Tuple[float, float]]:
    return merged((("", a, b - a) for a, b in intervals), lo, hi)


def idle_covered_share(trace, spans: Sequence[Span]) -> Optional[float]:
    """Share (%) of the first device's idle time in the window that falls
    inside at least one program span, on any thread."""
    cover = _union_ns(((s[1], s[1] + s[2]) for s in spans), trace.lo, trace.hi)
    idle = cover_ns = 0.0
    for a, b in trace.idle_gaps():
        idle += b - a
        cover_ns += sum(max(0.0, min(b, d) - max(a, c)) for c, d in cover)
    return 100.0 * cover_ns / idle if idle > 0 else None


def _inside(spans: Sequence[Span], outer: Span) -> List[Span]:
    """Spans on ``outer``'s thread that lie inside it, ``outer`` left out."""
    lo, hi = outer[1], outer[1] + outer[2]
    return [s for s in spans if s is not outer and s[3] == outer[3]
            and lo <= s[1] and s[1] + s[2] <= hi]


def self_ns(span: Span, spans: Sequence[Span]) -> float:
    """A span's duration less the time its thread spent in spans inside it."""
    kids = _inside(spans, span)
    busy = _union_ns(((s[1], s[1] + s[2]) for s in kids),
                     span[1], span[1] + span[2])
    return span[2] - sum(b - a for a, b in busy)


def job_decomposition(spans: Sequence[Span], lo: float = float("-inf"),
                      hi: float = float("inf")) -> Dict:
    """The ingest job worker's time, split by stage, over the jobs that
    started and ended in ``[lo, hi]``: per job on average, the self time of
    ``zllm.job`` and of each span on its thread inside it, and the summed
    durations of the spans other threads ran under the job's key (hashing
    and entropy coding on the pool, the HTTP receive)."""
    jobs = [s for s in spans if s[0] == JOB_SPAN and lo <= s[1]
            and s[1] + s[2] <= hi]
    worker: Dict[str, float] = {}
    other: Dict[str, float] = {}
    job_ns = job_self = 0.0
    for job in jobs:
        job_ns += job[2]
        own = _inside(spans, job)
        job_self += self_ns(job, spans)
        for s in own:
            worker[s[0]] = worker.get(s[0], 0.0) + self_ns(s, own)
        key = job[4].get("key")
        for s in spans:
            if s[3] != job[3] and s[4].get("key") == key and s[0] != JOB_SPAN:
                other[s[0]] = other.get(s[0], 0.0) + s[2]
    n = len(jobs)
    if n == 0:
        return {"jobs": 0}
    per = {k: v / n / 1e9 for k, v in sorted(worker.items(), key=lambda x: -x[1])}
    return {"jobs": n, "job_s": job_ns / n / 1e9,
            "job_self_share": 100.0 * job_self / job_ns if job_ns else None,
            "worker_self_s": dict(per, **{JOB_SPAN: job_self / n / 1e9}),
            "other_threads_s": {k: v / n / 1e9 for k, v in
                                sorted(other.items(), key=lambda x: -x[1])},
            "queued_s": sum(float(j[4].get("queued_s", 0.0)) for j in jobs) / n}
