"""Arithmetic shared by the metric readers in ``metrics/``.

Each reader takes the finished ``harness.Run`` and returns a number, or
None where the run holds nothing to read (an untraced run for a trace
metric, a window with no acknowledged upload)."""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence


def acked_in_window(run) -> List[Dict]:
    """Uploads acknowledged 200 before the window closed."""
    return [r for r in run.records
            if r["status"] == 200 and r["t_ack"] <= run.t_end]


def delta(run, *keys: str) -> float:
    """``stats1 - stats0`` at a path of keys in the server's /stats."""
    a, b = run.stats0, run.stats1
    for k in keys:
        a, b = a.get(k, {}), b.get(k, {})
    return float((b or 0) - (a or 0))


def idle_share(run) -> Optional[float]:
    if run.trace is None or run.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - run.trace.busy_s() / run.trace.window_s)


def changed_bytes(run) -> float:
    """Bytes of the tensors that the acknowledged uploads of the traced
    span changed from their base, as the generator made them: what the
    store's XOR split has to transform."""
    return float(sum(r["changed_bytes"] for r in run.records
                     if r["status"] == 200))


def roofline(run, programs: Sequence[str], nbytes: float) -> Optional[float]:
    """Least time at the chip's HBM bandwidth over the device time of the
    compiled transform programs (padding, relayout, the Pallas kernel and
    the slicing that one transform needs) whose names match ``programs``."""
    if run.trace is None or nbytes <= 0:
        return None
    t = run.trace.program_s(programs)
    if t <= 0:
        return None
    return 100.0 * (nbytes / run.peaks["hbm_bytes_per_s"]) / t


def window_compiles(run) -> Optional[float]:
    """Compiles between the window's start and its last answer; the
    read-back that follows is not counted."""
    return float(sum(1 for t in run.compiles if run.t0 <= t <= run.t_drain))
