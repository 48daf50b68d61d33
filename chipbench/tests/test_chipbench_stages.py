"""The program's stage spans and counters as the benchmark reads them:
spans from threads and from one event loop come back from the profiler's
trace with their keys, the six ``*_s_per_GB.ingest`` readers, idle gaps
labelled by the spans open in them, the idle time the spans cover, the
job worker's time split by stage, and a traced run at test size."""

import asyncio
import threading

import pytest

import tinycell
from bench import registry, stages
from bench.trace import Reduced, find_xplane, read_xplane
from repro import obs

READERS = {
    "receive_s_per_GB.ingest": ["zllm.http.receive"],
    "queue_wait_s_per_GB.ingest": ["zllm.job.queued"],
    "hash_s_per_GB.ingest": ["zllm.hash.file", "zllm.hash.tensor"],
    "array_s_per_GB.ingest": ["zllm.array.encode"],
    "entropy_s_per_GB.ingest": ["zllm.entropy"],
    "commit_s_per_GB.ingest": ["zllm.container.write", "zllm.index.save"],
}


def test_spans_from_threads_and_one_event_loop(tmp_path):
    import jax
    both_alive = threading.Barrier(2, timeout=30)

    def work(k):
        with obs.span("zllm.t.thread", key=f"r/t{k}", bytes=k):
            with obs.span("zllm.t.inner"):
                both_alive.wait()

    async def upload(k, delay):
        with obs.span("zllm.t.await", key=f"r/a{k}"):
            await asyncio.sleep(delay)

    async def both():
        await asyncio.gather(upload(1, 0.06), upload(2, 0.02))

    jax.profiler.start_trace(str(tmp_path))
    try:
        threads = [threading.Thread(target=work, args=(k,)) for k in (1, 2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        asyncio.run(both())
    finally:
        jax.profiler.stop_trace()
    assert not any(t.is_alive() for t in threads)
    spans = [s for s in stages.read_program_spans(find_xplane(str(tmp_path)))
             if s[0].startswith("zllm.t.")]
    by = {(s[0], s[4].get("key")): s for s in spans}
    assert set(by) == {("zllm.t.thread", "r/t1"), ("zllm.t.thread", "r/t2"),
                       ("zllm.t.inner", "r/t1"), ("zllm.t.inner", "r/t2"),
                       ("zllm.t.await", "r/a1"), ("zllm.t.await", "r/a2")}
    assert by[("zllm.t.thread", "r/t2")][4]["bytes"] == 2
    a1, a2 = by[("zllm.t.await", "r/a1")], by[("zllm.t.await", "r/a2")]
    # each await-crossing span is one event from its start to its end, and
    # the two overlap on the loop's thread
    assert a1[3] == a2[3] and a1[2] >= 0.06e9 and 0.02e9 <= a2[2] < a1[2]
    assert a1[1] <= a2[1] + a2[2] and a2[1] <= a1[1] + a1[2]
    assert by[("zllm.t.thread", "r/t1")][3] != by[("zllm.t.thread", "r/t2")][3]


class _Run:
    def __init__(self, stages0, stages1, raw0, raw1):
        self.stats0 = {"server": {} if stages0 is None else {"stages": stages0},
                       "store": {"raw_bytes": raw0}}
        self.stats1 = {"server": {} if stages1 is None else {"stages": stages1},
                       "store": {"raw_bytes": raw1}}


def _table(seconds):
    return {n: {"count": 1, "seconds": s, "bytes": 0} for n, s in seconds.items()}


@pytest.mark.parametrize("metric", sorted(READERS))
def test_stage_readers_are_seconds_per_user_gb(metric):
    read = registry.reader(metric)
    names = READERS[metric]
    before = _table({n: 1.0 for n in names} | {"zllm.job": 5.0})
    after = _table({n: 1.0 + 0.5 * (i + 1) for i, n in enumerate(names)}
                   | {"zllm.job": 50.0})
    want = sum(0.5 * (i + 1) for i in range(len(names))) / 2.0
    assert read(_Run(before, after, 10**9, 3 * 10**9)) == pytest.approx(want)
    # a stage first counted in the window
    assert read(_Run({}, after, 0, 2 * 10**9)) == pytest.approx(
        sum(after[n]["seconds"] for n in names) / 2.0)
    assert read(_Run(before, after, 10**9, 10**9)) is None      # no byte taken in
    assert read(_Run(None, None, 0, 10**9)) is None             # no counters


def test_benchmark_lists_the_stage_metrics_for_both_cells():
    bm = registry.load_benchmark()
    cells = [w["name"] for w in bm["workloads"]]
    got = {m["name"]: m for m in bm["per_layer"] if m["name"] in READERS}
    assert set(got) == set(READERS)
    for m in got.values():
        assert (m["unit"], m["better"], m["moves"], m["source"]) == (
            "s/GB", "lower", "ingest_MBps", "program_counter")
        assert m["workloads"] == cells


def _span(name, start, dur, line="L0", **stats):
    return [name, start, dur, line, stats]


WINDOW = Reduced({"devices": {"/device:TPU:0": [["%op", 400, 100]]},
                  "spans": {"chipbench.window": [0, 1000]}})
SPANS = [
    _span("zllm.job", 0, 900, key="r/a", queued_s=0.25),
    _span("zllm.decide", 50, 500, key="r/a"),
    _span("zllm.array.encode", 380, 140, key="r/a"),
    _span("zllm.index.save", 800, 60),
    _span("zllm.entropy", 100, 300, "L1", key="r/a"),
    _span("zllm.hash.tensor", 550, 100, "L2", key="r/a"),
    _span("zllm.http.receive", 0, 200, "L3", key="r/b"),
]


class _Traced:
    trace, t_span = WINDOW, 100.0
    records = [{"t_send": 99.0, "t_ack": 101.0},
               {"t_send": 100.0, "t_ack": 100.0000005},
               {"t_send": 100.8, "t_ack": 105.0}]


def test_gaps_are_labelled_with_the_spans_open_at_their_middle():
    gaps = stages.label_gaps(_Traced(), SPANS)
    # the gaps, longest first: [500, 1000) with its middle at 750, when the
    # second upload has been answered; [0, 400) with its middle at 200
    assert gaps == [["1 PUT in flight: zllm.job", 500e-9],
                    ["2 PUT in flight: zllm.decide, zllm.entropy", 400e-9]]
    assert stages.open_at(SPANS, 950) == []
    assert stages.open_at(SPANS, 390) == ["zllm.array.encode", "zllm.entropy"]


def test_idle_time_covered_by_program_spans():
    # idle [0, 400) is covered whole, [500, 1000) up to 900
    assert stages.idle_covered_share(WINDOW, SPANS) == pytest.approx(
        100.0 * (400 + 400) / 900)
    assert stages.idle_covered_share(WINDOW, []) == 0.0


def test_job_time_is_split_into_its_stages():
    d = stages.job_decomposition(SPANS)
    assert d["jobs"] == 1 and d["job_s"] == pytest.approx(900e-9)
    worker = d["worker_self_s"]
    assert worker["zllm.decide"] == pytest.approx(360e-9)   # 500 - 140 inside
    assert worker["zllm.array.encode"] == pytest.approx(140e-9)
    assert worker["zllm.index.save"] == pytest.approx(60e-9)
    assert worker["zllm.job"] == pytest.approx(340e-9)      # 900 - 500 - 60
    assert d["job_self_share"] == pytest.approx(100.0 * 340 / 900)
    assert d["other_threads_s"] == {"zllm.entropy": pytest.approx(300e-9),
                                    "zllm.hash.tensor": pytest.approx(100e-9)}
    assert d["queued_s"] == 0.25
    assert stages.job_decomposition(SPANS, 10, 1000) == {"jobs": 0}


def test_traced_run_at_test_size(tmp_path):
    """A traced run on the CPU: every stage metric reads a number, the
    spans account for the job worker's time, and the window's gaps get
    span labels."""
    from bench import harness
    traffic = {**registry.traffic("ft-ingest"), "uploads_per_client": 2}
    cell = {"name": "tiny.ft-ingest", "config": "tiny", "traffic": "ft-ingest",
            "chips": 1}
    trace_dir = str(tmp_path / "trace")
    run = harness.run_cell(cell, tinycell.DENSE, traffic, 2**33 + 11, 2.0,
                           str(tmp_path / "run"), trace_dir=trace_dir,
                           log=lambda m: None, gen_threads=2)
    assert harness.correct(run.checks)
    path = find_xplane(trace_dir)
    run.trace = Reduced(read_xplane(path))
    spans = stages.read_program_spans(path)
    names = {s[0] for s in spans}
    assert {"zllm.http.receive", "zllm.job", "zllm.decide", "zllm.hash.file",
            "zllm.array.encode", "zllm.array.device", "zllm.entropy",
            "zllm.container.write", "zllm.index.save"} <= names
    assert all(s[4].get("key") for s in spans if s[0] != "zllm.index.save")
    for metric in READERS:
        value = registry.reader(metric)(run)
        assert value is not None and value >= 0, metric
    d = stages.job_decomposition(spans, run.trace.lo, run.trace.hi)
    assert d["jobs"] >= 1 and d["job_self_share"] < 50
    assert stages.idle_covered_share(run.trace, spans) > 50
    assert any(": zllm." in label for label, _ in stages.label_gaps(run, spans))
