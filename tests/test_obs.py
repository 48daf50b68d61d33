"""Stage spans and counters (repro.obs) and where the ingest path records
them: counters with the profiler off, trace events with it on, keys
inherited by nested spans, the stages an HTTP upload moves in /stats, and
the store-clock timing they replaced, gone from old indexes too."""

import glob
import json
import os
import subprocess
import sys
import threading
import urllib.request

import numpy as np

from repro import obs
from repro.core.pipeline import ZLLMStore
from repro.formats import safetensors as st
from repro.serve.store_server import ServerThread

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")


def _moved(before, after, name, field="count"):
    return after.get(name, {}).get(field, 0) - before.get(name, {}).get(field, 0)


def test_span_off_counts_and_loads_no_jax():
    code = (
        "import json, sys\n"
        "from repro import obs\n"
        "import repro.core.pipeline\n"
        "with obs.span('zllm.t.outer', key='r/f', bytes=10) as sp:\n"
        "    with obs.span('zllm.t.inner', bytes=3) as inner:\n"
        "        inner.set(bytes=4)\n"
        "    sp.set(out=2)\n"
        "obs.add('zllm.t.wait', 0.5)\n"
        "print(json.dumps({'jax': 'jax' in sys.modules, 'stages': obs.stages()}))\n")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120, env=dict(os.environ, PYTHONPATH=SRC))
    assert out.returncode == 0, out.stderr
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["jax"] is False
    st_ = got["stages"]
    assert st_["zllm.t.outer"]["count"] == 1 and st_["zllm.t.outer"]["bytes"] == 10
    assert st_["zllm.t.inner"]["bytes"] == 4      # set() at the end counts
    assert st_["zllm.t.outer"]["seconds"] >= st_["zllm.t.inner"]["seconds"] > 0
    assert st_["zllm.t.wait"] == {"count": 1, "seconds": 0.5, "bytes": 0}


def _program_events(logdir):
    from jax.profiler import ProfileData
    path = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)[-1]
    return [(e.name, dict(e.stats)) for plane in ProfileData.from_file(path).planes
            for line in plane.lines for e in line.events
            if e.name.startswith("zllm.t.")]


def test_span_emits_events_only_while_tracing(tmp_path):
    import jax
    with obs.span("zllm.t.before", key="a/x"):
        pass
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.span("zllm.t.traced", key="a/y", bytes=7) as sp:
            with obs.span("zllm.t.nested"):
                pass
            sp.set(out=3)
    finally:
        jax.profiler.stop_trace()
    with obs.span("zllm.t.after", key="a/z"):
        pass
    events = dict(_program_events(str(tmp_path)))
    assert set(events) == {"zllm.t.traced", "zllm.t.nested"}
    assert events["zllm.t.traced"] == {"key": "a/y", "bytes": 7, "out": 3}
    assert events["zllm.t.nested"] == {"key": "a/y"}   # inherited


def test_key_is_inherited_per_thread_and_never_leaks():
    seen = {}

    def child(name):
        with obs.span("zllm.t.child") as sp:
            seen[name] = sp.stats.get("key")

    with obs.span("zllm.t.parent", key="r/outer"):
        child("nested")
        t = threading.Thread(target=child, args=("thread",))
        t.start()
        t.join(timeout=30)
        assert not t.is_alive()
    child("after")
    assert seen == {"nested": "r/outer", "thread": None, "after": None}


def _write(path, tensors):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    st.save_file(tensors, path)
    return open(path, "rb").read()


def _put(srv, path, body):
    req = urllib.request.Request(f"http://{srv.host}:{srv.port}{path}",
                                 data=body, method="PUT")
    with urllib.request.urlopen(req, timeout=120) as r:
        return json.loads(r.read())


def _get_json(srv, path):
    with urllib.request.urlopen(f"http://{srv.host}:{srv.port}{path}",
                                timeout=60) as r:
        return json.loads(r.read())


def test_put_sync_moves_the_stage_counters(tmp_path):
    rng = np.random.RandomState(3)
    base = {f"model.l{i}.weight": (rng.randn(256, 64) * 0.02).astype(np.float32)
            for i in range(4)}
    ft = {k: (v * (1 + rng.randn(*v.shape) * 1e-3)).astype(np.float32)
          for k, v in base.items()}
    base_b = _write(str(tmp_path / "src" / "base.safetensors"), base)
    ft_b = _write(str(tmp_path / "src" / "ft.safetensors"), ft)
    with ZLLMStore(str(tmp_path / "store"), backend="numpy", workers=2) as store:
        with ServerThread(store) as srv:
            before = _get_json(srv, "/stats")["server"]["stages"]
            out = _put(srv, "/repo/org/base/file/model.safetensors?sync=1", base_b)
            assert out["job"]["state"] == "done", out
            out = _put(srv, "/repo/u/ft/file/model.safetensors?sync=1&base=org/base",
                       ft_b)
            assert out["job"]["state"] == "done", out
            stats = _get_json(srv, "/stats")
    after = stats["server"]["stages"]
    for name in ("zllm.http.receive", "zllm.job", "zllm.job.queued",
                 "zllm.hash.file", "zllm.decide", "zllm.index.save"):
        assert _moved(before, after, name) == 2, name
    assert _moved(before, after, "zllm.http.receive", "bytes") == len(base_b) + len(ft_b)
    assert _moved(before, after, "zllm.hash.file", "bytes") == len(base_b) + len(ft_b)
    assert _moved(before, after, "zllm.hash.tensor") == 8
    assert _moved(before, after, "zllm.entropy") == 8
    assert _moved(before, after, "zllm.entropy", "bytes") == 2 * 4 * 256 * 64 * 4
    assert _moved(before, after, "zllm.container.write") == 2
    assert _moved(before, after, "zllm.job", "seconds") > 0
    # the store's summary no longer carries its own ingest clock
    assert not any("throughput" in k for k in stats["store"])


def test_old_index_with_store_clock_timing_loads(tmp_path):
    rng = np.random.RandomState(5)
    src = str(tmp_path / "m.safetensors")
    data = _write(src, {"w": rng.randn(512).astype(np.float32)})
    root = str(tmp_path / "store")
    with ZLLMStore(root, workers=1) as store:
        store.ingest_file(src, "org/m")
        store.save_index()
    path = os.path.join(root, "index.json")
    idx = json.load(open(path))
    idx["stats"]["ingest_seconds"] = 1.25      # as written before the counters
    json.dump(idx, open(path, "w"))
    with ZLLMStore(root, workers=1) as store:
        assert store.load_index()
        assert "ingest_seconds" not in vars(store.stats)
        assert store.stats.raw_bytes == len(data)
        assert store.retrieve_file("org/m", "m.safetensors") == data
        store.save_index()
    assert "ingest_seconds" not in json.load(open(path))["stats"]
