"""The trace reduction: busy time as a union of intervals, program time,
idle gaps and the roofline arithmetic, on hand-made events and on a trace
recorded on one TPU v5e with ``run.py --save-trace`` (``trace_v5e_ingest.json``:
the device ops and programs of a 30-second window of
``qwen2-7b-hub.ft-ingest`` that took in 7 uploads)."""

import json
import os

import pytest

import tinycell  # noqa: F401
from bench import registry
from bench.trace import Reduced, busy_ns, idle_gaps, matched_ns, merged, top_ops

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "trace_v5e_ingest.json")
XOR = "%xor_split_2d.1 = (u8[8,1024]) custom-call(u16[8,1024] %a, u16[8,1024] %b)"

EVENTS = [("%fusion.1 = f()", 0, 10), (XOR, 5, 10), ("%copy = c()", 30, 5),
          (XOR, 100, 20), ("%late = l()", 190, 50)]


def test_busy_is_the_union_clipped_to_the_window():
    assert merged(EVENTS, 0, 200) == [(0, 15), (30, 35), (100, 120), (190, 200)]
    assert busy_ns(EVENTS, 0, 200) == 15 + 5 + 20 + 10
    assert busy_ns(EVENTS, 8, 32) == 7 + 2


def test_matching_sums_the_named_events():
    assert matched_ns(EVENTS, [r"^%xor_split_2d\b"], 0, 200) == 30
    assert matched_ns(EVENTS, [r"^%xor_split_2d\b", r"^%copy\b"], 0, 110) == 10 + 5 + 10
    assert matched_ns(EVENTS, [r"^%merge_xor_2d\b"], 0, 200) == 0


def test_idle_gaps_longest_first_and_top_ops():
    gaps = idle_gaps(EVENTS, 0, 200)
    assert gaps == [(120, 190), (35, 100), (15, 30)]
    assert sum(b - a for a, b in gaps) + busy_ns(EVENTS, 0, 200) == 200
    assert top_ops(EVENTS, 0, 200, k=1) == [(XOR, 30e-9)]
    assert len(top_ops(EVENTS, 0, 200)) == 4


MODULES = [("jit_bitx_encode_planes(1)", 0, 40), ("jit_other(2)", 50, 10),
           ("jit_bitx_decode_planes(3)", 100, 20)]


class _Run:
    def __init__(self, trace, *changed):
        self.trace, self.peaks = trace, registry.peaks("TPU v5 lite")
        self.records = [{"status": 200, "changed_bytes": n} for n in changed]
        self.records.append({"status": 500, "changed_bytes": 10**12})


def test_roofline_is_least_time_over_program_time():
    tr = Reduced({"devices": {"/device:TPU:0": [list(e) for e in EVENTS]},
                  "modules": {"/device:TPU:0": [list(e) for e in MODULES]},
                  "spans": {"chipbench.window": [0, 200]}})
    assert tr.window_s == 200e-9 and tr.busy_s() == 50e-9
    split = registry.reader("xor_split_roofline")
    # 40 ns of encode program; 3 bytes moved per changed tensor byte at
    # 819 GB/s, over the acknowledged uploads only
    half = 819e9 * 40e-9 / 3 / 2
    assert split(_Run(tr, half / 4, 3 * half / 4)) == pytest.approx(50.0)
    assert split(_Run(tr)) is None
    assert split(_Run(None, half)) is None
    assert registry.reader("device_idle_share.ingest")(_Run(tr)) == pytest.approx(75.0)


def test_recorded_v5e_trace():
    with open(RECORDED) as f:
        data = json.load(f)
    tr = Reduced(data)
    assert list(tr.devices) == ["/device:TPU:0"]
    assert tr.window_s == pytest.approx(38.133204999)
    assert tr.busy_s() == pytest.approx(0.064321258)
    gaps = tr.idle_gaps()
    assert sum(b - a for a, b in gaps) / 1e9 == pytest.approx(
        tr.window_s - tr.busy_s(), rel=1e-9)
    assert gaps[0][1] - gaps[0][0] > 1e9          # seconds of host work
    # 7 uploads of 466129920 tensor bytes went through the encode program
    # (the store's own tally, saved with the trace)
    assert data["moved"]["xor_split"] == 7 * 466129920
    program = tr.program_s(["bitx_encode_planes"])
    kernel = matched_ns(tr.all_events(), [r"^%xor_split_2d\b"], tr.lo, tr.hi) / 1e9
    assert 0 < kernel < program < 2 * tr.busy_s()
    share = registry.reader("xor_split_roofline")(_Run(tr, *[466129920] * 7))
    assert share == pytest.approx(18.580061969924348)
    assert tr.top_ops(1)[0][0].startswith("%xor_split_2d")
