"""BENCHMARK.json's names and units, the peaks table, the registry's lookup
by name, and the arithmetic of the end-to-end metrics."""

import json
import os
import re

import pytest

import tinycell
from bench import harness, registry, spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bm():
    return registry.load_benchmark()


def test_names_units_and_files(bm):
    names = [m["name"] for m in bm["end_to_end"] + bm["per_layer"]]
    names += [w["name"] for w in bm["workloads"]] + [c["name"] for c in bm["configs"]]
    assert len(names) == len(set(names))
    for n in names + [w["traffic"] for w in bm["workloads"]]:
        assert NAME.match(n), n
    for m in bm["end_to_end"] + bm["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert os.path.exists(os.path.join(registry.BENCH_DIR, "metrics",
                                           m["name"] + ".py")), m["name"]
    assert {m["name"] for m in bm["end_to_end"]} >= {"setup_s"}
    e2e = {m["name"] for m in bm["end_to_end"]}
    for m in bm["per_layer"]:
        assert m["moves"] in e2e
    for w in bm["workloads"]:
        assert w["chips"] == 1
        registry.config(bm, w["config"])
        assert registry.traffic(w["traffic"])["uploads_per_client"] >= 1
        assert len(registry.metrics_for(bm, w["name"], traced=False)) >= 2
        assert registry.metrics_for(bm, w["name"], traced=True)


def test_peaks_table_rejects_unknown_device():
    assert registry.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        registry.peaks("TPU v9 imaginary")


def test_registry_finds_new_files_by_name(tmp_path, monkeypatch):
    (tmp_path / "traffic").mkdir()
    (tmp_path / "metrics").mkdir()
    (tmp_path / "configs").mkdir()
    (tmp_path / "traffic" / "burst-ingest.json").write_text(
        json.dumps({"clients": 4, "uploads_per_client": 9}))
    (tmp_path / "metrics" / "answer.read.py").write_text(
        "def read(run):\n    return 42.0\n")
    (tmp_path / "configs" / "new.json").write_text(
        json.dumps(dict(tinycell.DENSE, model_type="new_arch")))
    (tmp_path / "layouts").mkdir()
    (tmp_path / "layouts" / "new_arch.py").write_text(
        "from bench.spec import Tensor\n\n\n"
        "def layer_tensors(cfg):\n"
        "    return [Tensor('w', (cfg['hidden_size'],), 'F32')]\n")
    monkeypatch.setattr(registry, "BENCH_DIR", str(tmp_path))
    monkeypatch.setattr(registry, "REPO", str(tmp_path))
    bm = {"configs": [{"name": "new", "file": "configs/new.json"}]}
    assert registry.traffic("burst-ingest")["uploads_per_client"] == 9
    assert registry.reader("answer.read")(None) == 42.0
    cfg = registry.config(bm, "new")
    assert cfg["hidden_size"] == 256
    assert spec.layer_tensors(cfg) == [spec.Tensor("w", (256,), "F32")]


def test_unknown_model_type_names_the_missing_layout():
    missing = os.path.join(registry.BENCH_DIR, "layouts", "no_such_arch.py")
    with pytest.raises(FileNotFoundError, match=re.escape(missing)):
        spec.layer_tensors(dict(tinycell.DENSE, model_type="no_such_arch"))


class _Run:
    def __init__(self, **kw):
        self.__dict__.update(kw)


def test_ingest_rate_is_bytes_over_window_start_to_last_ack():
    read = registry.reader("ingest_MBps")
    recs = [{"status": 200, "bytes": 4e6, "t_ack": 12.0},
            {"status": 200, "bytes": 6e6, "t_ack": 14.0},
            {"status": 500, "bytes": 9e6, "t_ack": 13.0},
            {"status": 200, "bytes": 8e6, "t_ack": 21.0}]  # after the window
    run = _Run(records=recs, t0=10.0, t_end=20.0)
    assert read(run) == pytest.approx(10e6 / 4.0 / 1e6)
    assert read(_Run(records=[], t0=0.0, t_end=1.0)) is None


def test_stored_per_user_byte_counts_every_acknowledged_upload():
    read = registry.reader("stored_per_user_byte")
    recs = [{"status": 200, "bytes": 100}, {"status": 500, "bytes": 900},
            {"status": 200, "bytes": 300}]
    assert read(_Run(records=recs, stored0=1000, stored1=1100)) == pytest.approx(0.25)
    assert read(_Run(records=recs[1:2], stored0=0, stored1=5)) is None


def test_dedup_share_adds_file_dedup_to_tensor_dedup():
    read = registry.reader("dedup_byte_share.ingest")
    stats = lambda raw, dedup: {"store": {"raw_bytes": raw,  # noqa: E731
                                          "codec_bytes": {"dedup": dedup}}}
    recs = [{"status": 200, "bytes": 50, "row": {"file_dedup_hit": True}},
            {"status": 200, "bytes": 150, "row": {}}]
    run = _Run(records=recs, stats0=stats(1000, 10), stats1=stats(1200, 110))
    assert read(run) == pytest.approx(100.0 * (100 + 50) / 200)
    assert read(_Run(records=[], stats0=stats(5, 0), stats1=stats(5, 0))) is None


def test_correct_rule():
    assert harness.correct({"a": {"value": 0, "limit": 0, "rule": "<="},
                            "b": {"value": 3, "limit": 1, "rule": ">="}})
    assert not harness.correct({"a": {"value": 1, "limit": 0, "rule": "<="}})
    assert not harness.correct({"b": {"value": 0, "limit": 1, "rule": ">="}})


def _run_cli(cwd, env_extra):
    import subprocess
    import sys
    env = dict(os.environ, **env_extra)
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "qwen2-7b-hub.ft-ingest",
         "--seed", str(2**33 + 3), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_cli_fails_without_a_tpu():
    out = _run_cli(registry.REPO, {"JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "needs 1 TPU" in out.stderr


def test_cli_fails_with_only_the_benchmark_files(tmp_path):
    import shutil
    shutil.copy(os.path.join(registry.REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(registry.BENCH_DIR, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("work", "__pycache__"))
    out = _run_cli(str(tmp_path), {"JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    assert out.stdout.strip() == ""


def test_window_compiles_stop_at_the_last_answer():
    read = registry.reader("window_compiles.ingest")
    run = _Run(compiles=[9.0, 10.5, 30.0, 41.0], t0=10.0, t_drain=35.0, t_end=60.0)
    assert read(run) == 2.0   # not the set-up's, nor the read-back's
