"""Parallel ingest/retrieval engine tests: bit-identical containers across
worker counts, single-hash-pass base maps, cache invalidation, persistence
of tensor-dedup state, and fresh-process retrieval."""

import hashlib
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core.bitx import BitXCodec, BitXReader, BitXWriter
from repro.core.dedup import FileDedup, sha256_file
from repro.core import pipeline as pipeline_mod
from repro.core.pipeline import ZLLMStore
from repro.formats import safetensors as st

# src/ directory (repro may be a namespace package, so derive from a module)
SRC_DIR = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(pipeline_mod.__file__))))


def _write_model(path, rng, n_tensors=6, n=2048, scale=0.02):
    tensors = {f"model.t{i}.weight": (rng.randn(n) * scale).astype(np.float32)
               for i in range(n_tensors)}
    os.makedirs(os.path.dirname(path), exist_ok=True)
    st.save_file(tensors, path)
    return tensors


def _write_finetune(path, base_tensors, rng, sigma=1e-3):
    ft = {k: (v + rng.randn(*v.shape).astype(np.float32) * sigma).astype(np.float32)
          for k, v in base_tensors.items()}
    os.makedirs(os.path.dirname(path), exist_ok=True)
    st.save_file(ft, path)
    return ft


def _container_bytes(store_root):
    out = {}
    croot = os.path.join(store_root, "containers")
    for dirpath, _, files in os.walk(croot):
        for fn in files:
            p = os.path.join(dirpath, fn)
            out[os.path.relpath(p, croot)] = open(p, "rb").read()
    return out


# ---------------------------------------------------------------------------
# Tentpole: parallel == serial, bit for bit
# ---------------------------------------------------------------------------

def test_parallel_ingest_bit_identical_to_serial(tmp_path, corpus_dir):
    """Same corpus through workers∈{1,4} ⇒ byte-identical .bitx containers
    (the ordered-merge determinism rule), and bit-exact retrieval."""
    root, manifest = corpus_dir
    stores = {}
    for w in (1, 4):
        s = ZLLMStore(str(tmp_path / f"store-w{w}"), workers=w)
        for rid, kind in manifest:
            s.ingest_repo(os.path.join(root, rid), rid)
        stores[w] = s

    c1 = _container_bytes(str(tmp_path / "store-w1"))
    c4 = _container_bytes(str(tmp_path / "store-w4"))
    assert c1.keys() == c4.keys() and len(c1) > 0
    for name in c1:
        assert c1[name] == c4[name], f"container diverged: {name}"

    # parallel retrieval reconstructs bit-exactly (verify=True checks sha256)
    for rid, kind in manifest:
        orig = open(os.path.join(root, rid, "model.safetensors"), "rb").read()
        assert stores[4].retrieve_file(rid, "model.safetensors") == orig
    for s in stores.values():
        s.close()


def test_parallel_stats_match_serial(tmp_path, corpus_dir):
    root, manifest = corpus_dir
    summaries = {}
    for w in (1, 4):
        s = ZLLMStore(str(tmp_path / f"stat-w{w}"), workers=w)
        for rid, kind in manifest:
            s.ingest_repo(os.path.join(root, rid), rid)
        summaries[w] = s.summary()
        s.close()
    for key in ("raw_bytes", "stored_bytes", "reduction_ratio", "file_dedup_hits",
                "tensor_dedup"):
        assert summaries[1][key] == summaries[4][key], key


def test_pipelined_multifile_bit_identical_to_serial(tmp_path, corpus_dir):
    """The multi-file extension of the workers-1-vs-4 equivalence: the whole
    corpus through ONE cross-file pipelined ingest_many batch (stage A
    prefetch + deferred writes) must produce byte-identical containers to
    per-file serial ingest, with results in submission order."""
    root, manifest = corpus_dir
    uploads = [(os.path.join(root, rid, "model.safetensors"), rid)
               for rid, _ in manifest]

    s_serial = ZLLMStore(str(tmp_path / "serial"), workers=1)
    serial_results = [s_serial.ingest_file(p, rid) for p, rid in uploads]

    s_pipe = ZLLMStore(str(tmp_path / "pipe"), workers=4, pipeline_depth=3)
    pipe_results = s_pipe.ingest_many(uploads)

    c1 = _container_bytes(str(tmp_path / "serial"))
    c2 = _container_bytes(str(tmp_path / "pipe"))
    assert c1.keys() == c2.keys() and len(c1) > 0
    for name in c1:
        assert c1[name] == c2[name], f"pipelined container diverged: {name}"

    # per-upload decisions match in submission order
    assert len(pipe_results) == len(serial_results)
    for rs, rp in zip(serial_results, pipe_results):
        for f in ("repo_id", "filename", "raw_bytes", "stored_bytes",
                  "file_dedup_hit", "near_dup_hit", "base_id", "n_tensors",
                  "n_dedup", "n_bitx", "n_zipnn", "n_raw"):
            assert getattr(rs, f) == getattr(rp, f), f
    # aggregate stats and retrieval match too
    for key in ("raw_bytes", "stored_bytes", "reduction_ratio",
                "file_dedup_hits", "near_dup_hits", "tensor_dedup"):
        assert s_serial.summary()[key] == s_pipe.summary()[key], key
    for p, rid in uploads:
        assert s_pipe.retrieve_file(rid, "model.safetensors") == open(p, "rb").read()
    s_serial.close()
    s_pipe.close()


def test_ingest_repos_cross_repo_pipeline_matches_per_repo(tmp_path, corpus_dir):
    root, manifest = corpus_dir
    s_a = ZLLMStore(str(tmp_path / "per-repo"), workers=1)
    for rid, _ in manifest:
        s_a.ingest_repo(os.path.join(root, rid), rid)
    s_b = ZLLMStore(str(tmp_path / "cross"), workers=4)
    s_b.ingest_repos([(os.path.join(root, rid), rid) for rid, _ in manifest])
    ca, cb = _container_bytes(str(tmp_path / "per-repo")), _container_bytes(str(tmp_path / "cross"))
    assert ca.keys() == cb.keys() and all(ca[k] == cb[k] for k in ca)
    s_a.close()
    s_b.close()


def test_process_entropy_backend_bit_identical(tmp_path):
    """Opt-in ProcessPoolExecutor entropy stage: same containers, bit for
    bit, as the in-thread entropy path (frames are pure functions of
    bytes/level/threads). Skips nothing: if fork is unavailable the store
    degrades to threads and the assertion still holds."""
    rng = np.random.RandomState(21)
    base_dir = str(tmp_path / "hub" / "org" / "b")
    base = _write_model(os.path.join(base_dir, "model.safetensors"), rng,
                        n_tensors=4, n=65536 // 4)
    ft_dir = str(tmp_path / "hub" / "u" / "ft")
    _write_finetune(os.path.join(ft_dir, "model.safetensors"), base, rng)
    uploads = [(os.path.join(base_dir, "model.safetensors"), "org/b"),
               (os.path.join(ft_dir, "model.safetensors"), "u/ft")]

    s_thread = ZLLMStore(str(tmp_path / "threads"), workers=2)
    s_thread.ingest_many(uploads)
    s_proc = ZLLMStore(str(tmp_path / "procs"), workers=2, entropy_procs=2)
    s_proc.ingest_many(uploads)

    ct = _container_bytes(str(tmp_path / "threads"))
    cp = _container_bytes(str(tmp_path / "procs"))
    assert ct.keys() == cp.keys() and len(ct) == 2
    for name in ct:
        assert ct[name] == cp[name], f"entropy-procs container diverged: {name}"
    s_thread.close()
    s_proc.close()


def test_entropy_worker_probe_detects_an_initialised_backend():
    """The initializer every spawned entropy worker runs raises once a JAX
    backend is initialised in its process (here: the test process's own)."""
    import jax
    from repro.core.pipeline import _entropy_worker_holds_jax, _entropy_worker_init
    jax.devices()
    assert _entropy_worker_holds_jax()
    with pytest.raises(RuntimeError, match="JAX backend"):
        _entropy_worker_init()


def test_pipelined_write_failure_rolls_back_cleanly(tmp_path, monkeypatch):
    """A failed deferred container write must not leave the index pointing
    at a container that never landed: the batch raises, the failed upload's
    decisions are rolled back — including a later upload that whole-file-
    dedup'd against the failed container — earlier uploads stay
    retrievable, fsck is clean."""
    import shutil
    import time as time_mod
    rng = np.random.RandomState(31)
    dirs = []
    for i in range(3):
        d = str(tmp_path / "hub" / f"org{i}" / "m")
        _write_model(os.path.join(d, "model.safetensors"),
                     np.random.RandomState(100 + i), scale=1.0)
        dirs.append(d)
    # upload 3: byte-identical to upload 1 → file-dedup pin against the
    # container whose write is about to fail
    dup_dir = str(tmp_path / "hub" / "org3" / "m")
    os.makedirs(dup_dir, exist_ok=True)
    shutil.copyfile(os.path.join(dirs[1], "model.safetensors"),
                    os.path.join(dup_dir, "model.safetensors"))
    dirs.append(dup_dir)
    uploads = [(os.path.join(d, "model.safetensors"), f"org{i}/m")
               for i, d in enumerate(dirs)]

    store = ZLLMStore(str(tmp_path / "store"), workers=2, pipeline_depth=2)
    from repro.core.bitx import BitXWriter
    real_write = BitXWriter.write
    calls = []

    def failing_write(self, path):
        calls.append(path)
        if len(calls) == 2:  # second container write blows up (disk full);
            # the sleep lets the decision stage reach the dedup upload first
            time_mod.sleep(0.5)
            raise OSError(28, "No space left on device")
        return real_write(self, path)

    monkeypatch.setattr(BitXWriter, "write", failing_write)
    with pytest.raises(OSError):
        store.ingest_many(uploads)
    monkeypatch.setattr(BitXWriter, "write", real_write)

    # upload 0 committed; 1 (failed), 2 (poisoned suffix) and 3 (dedup pin
    # into the failed container) all rolled back
    assert "org0/m/model.safetensors" in store.file_index
    for i in (1, 2, 3):
        assert f"org{i}/m/model.safetensors" not in store.file_index, i
    assert len(store.results) == store.stats.n_files == 1
    assert store.retrieve_file("org0/m", "model.safetensors") == \
        open(uploads[0][0], "rb").read()
    report = store.fsck(repair=False, spot_check=None)
    assert report.ok and not report.orphans, report.summary()
    # the rolled-back uploads re-ingest cleanly afterwards; the dup now
    # dedups against upload 1's NEW (successful) container
    res = store.ingest_many(uploads[1:])
    assert [r.file_dedup_hit for r in res] == [False, False, True]
    for p, rid in uploads[1:]:
        assert store.retrieve_file(rid, "model.safetensors") == open(p, "rb").read()
    store.close()


def test_gc_during_ingest_batch_serializes_safely(tmp_path):
    """gc()/delete from another thread during an ingest batch must
    serialize behind the admin lock — never corrupt index/lifecycle state
    mid-decision."""
    import threading
    paths = []
    for i in range(6):
        p = str(tmp_path / "hub" / f"org{i}" / "m" / "model.safetensors")
        _write_model(p, np.random.RandomState(300 + i), scale=1.0)
        paths.append((p, f"org{i}/m"))
    store = ZLLMStore(str(tmp_path / "store"), workers=2, pipeline_depth=2)
    store.ingest_file(*paths[0])
    store.delete_repo("org0")          # something for gc to reclaim

    sweeps = []
    t = threading.Thread(target=lambda: sweeps.append(store.gc()))
    t.start()                          # races the batch below for the lock
    store.ingest_many(paths[1:])
    t.join(timeout=60)
    assert sweeps and sweeps[0]["collected"] in (0, 1)
    store.gc()                         # idempotent follow-up sweep
    for p, rid in paths[1:]:
        assert store.retrieve_file(rid, "model.safetensors") == open(p, "rb").read()
    report = store.fsck(repair=False, spot_check=None)
    assert report.ok and not report.orphans, report.summary()
    store.close()


def test_failed_batch_reregistering_key_twice_leaves_no_dangling_entry(
        tmp_path, monkeypatch):
    """Regression (found in review): a batch that ingests the SAME key twice
    and fails must not 'restore' the second upload's index entry to the
    first upload's generation — that generation was rolled back moments
    earlier. The key must simply vanish and the bytes re-ingest cleanly."""
    v1_path = str(tmp_path / "v1" / "model.safetensors")
    v2_path = str(tmp_path / "v2" / "model.safetensors")
    _write_model(v1_path, np.random.RandomState(51), scale=1.0)
    _write_model(v2_path, np.random.RandomState(52), scale=1.0)
    v1 = open(v1_path, "rb").read()

    store = ZLLMStore(str(tmp_path / "store"), workers=2, pipeline_depth=2)
    from repro.core.bitx import BitXWriter
    monkeypatch.setattr(BitXWriter, "write",
                        lambda self, path: (_ for _ in ()).throw(
                            OSError(28, "No space left on device")))
    with pytest.raises(OSError):
        store.ingest_many([(v1_path, "org/m"), (v2_path, "org/m")])
    monkeypatch.undo()

    assert "org/m/model.safetensors" not in store.file_index
    assert not store.results and store.stats.n_files == 0
    report = store.fsck(repair=False, spot_check=None)
    assert report.ok and not report.orphans, report.summary()
    # v1's bytes must re-ingest as fresh content, not dedup against a ghost
    res = store.ingest_file(v1_path, "other/m")
    assert not res.file_dedup_hit
    assert store.retrieve_file("other/m", "model.safetensors") == v1
    store.close()


def test_stage_b_failure_releases_file_hash_registration(tmp_path, monkeypatch):
    """Regression (found in review): a stage-B failure BEFORE the pending
    write exists must release the upload's whole-file hash registration —
    otherwise a later identical upload false-dedups against the key's old
    generation (different bytes)."""
    v1_dir = str(tmp_path / "v1" / "org")
    _write_model(os.path.join(v1_dir, "model.safetensors"),
                 np.random.RandomState(61), scale=1.0)
    v1 = open(os.path.join(v1_dir, "model.safetensors"), "rb").read()
    v2_path = str(tmp_path / "v2" / "model.safetensors")
    _write_model(v2_path, np.random.RandomState(62), scale=1.0)
    v2 = open(v2_path, "rb").read()

    store = ZLLMStore(str(tmp_path / "store"))
    store.ingest_repo(v1_dir, "org")

    real_plan = ZLLMStore._plan_tensors
    monkeypatch.setattr(ZLLMStore, "_plan_tensors",
                        lambda *a, **k: (_ for _ in ()).throw(
                            OSError("source truncated under ingest")))
    with pytest.raises(OSError):
        store.ingest_file(v2_path, "org")   # failed re-registration, stage B
    monkeypatch.setattr(ZLLMStore, "_plan_tensors", real_plan)

    assert store.retrieve_file("org", "model.safetensors") == v1
    # v2's bytes must ingest FRESH under another key, not dedup to org@old
    res = store.ingest_file(v2_path, "other/m")
    assert not res.file_dedup_hit
    assert store.retrieve_file("other/m", "model.safetensors") == v2
    report = store.fsck(repair=False, spot_check=None)
    assert report.ok, report.summary()
    store.close()


def test_failed_reregistration_write_restores_previous_entry(tmp_path, monkeypatch):
    """Regression (found in review): rolling back a FAILED re-registration
    write must restore the key's previous index record — the old generation
    is still on disk and must stay retrievable, and gc() must not reclaim
    it."""
    rng = np.random.RandomState(41)
    v1_dir = str(tmp_path / "v1" / "org")
    _write_model(os.path.join(v1_dir, "model.safetensors"), rng, scale=1.0)
    v1 = open(os.path.join(v1_dir, "model.safetensors"), "rb").read()
    v2_path = str(tmp_path / "v2" / "model.safetensors")
    _write_model(v2_path, np.random.RandomState(99), scale=1.0)

    store = ZLLMStore(str(tmp_path / "store"), workers=2)
    store.ingest_repo(v1_dir, "org")

    from repro.core.bitx import BitXWriter
    monkeypatch.setattr(BitXWriter, "write",
                        lambda self, path: (_ for _ in ()).throw(
                            OSError(28, "No space left on device")))
    with pytest.raises(OSError):
        store.ingest_file(v2_path, "org")
    monkeypatch.undo()

    # the key still serves the OLD generation, and gc reclaims nothing
    assert store.retrieve_file("org", "model.safetensors") == v1
    assert store.gc()["collected"] == 0
    assert store.retrieve_file("org", "model.safetensors") == v1
    report = store.fsck(repair=False, spot_check=None)
    assert report.ok and not report.orphans, report.summary()
    # whole-file dedup still recognizes the old bytes
    copy_path = str(tmp_path / "copy" / "model.safetensors")
    os.makedirs(os.path.dirname(copy_path), exist_ok=True)
    open(copy_path, "wb").write(v1)
    assert store.ingest_file(copy_path, "mirror").file_dedup_hit
    # and the re-registration succeeds once the disk recovers
    res = store.ingest_file(v2_path, "org")
    assert not res.file_dedup_hit
    assert store.retrieve_file("org", "model.safetensors") == \
        open(v2_path, "rb").read()
    store.close()


# ---------------------------------------------------------------------------
# Base-map cache: one hash pass per base, ever
# ---------------------------------------------------------------------------

def test_base_hashed_exactly_once_for_k_finetunes(tmp_path):
    rng = np.random.RandomState(0)
    n_tensors, K = 6, 4
    base_dir = str(tmp_path / "hub" / "org" / "base")
    base = _write_model(os.path.join(base_dir, "model.safetensors"), rng, n_tensors)

    store = ZLLMStore(str(tmp_path / "store"), workers=2)
    store.ingest_repo(base_dir, "org/base")
    assert store.tensor_dedup.hash_calls == n_tensors  # the ONE base hash pass

    for k in range(K):
        ft_dir = str(tmp_path / "hub" / f"u{k}" / "ft")
        _write_finetune(os.path.join(ft_dir, "model.safetensors"), base, rng)
        store.ingest_file(os.path.join(ft_dir, "model.safetensors"),
                          f"u{k}/ft", declared_base="org/base")

    # K fine-tunes hashed their own tensors only — the base was never re-read
    assert store.tensor_dedup.hash_calls == n_tensors * (1 + K)
    assert store.base_map_stats == {"hits": K, "misses": 0, "primed": 1,
                                    "invalidations": 0}
    assert all(r.n_bitx > 0 for r in store.results[1:])
    store.close()


def test_base_map_invalidated_on_reregistration(tmp_path):
    """Re-ingesting a new standalone file under an existing key must drop the
    cached base map; later fine-tunes delta against the NEW base bytes."""
    rng = np.random.RandomState(1)
    key_id = "orgX/model.safetensors"
    v1_dir = str(tmp_path / "v1" / "orgX")
    v1 = _write_model(os.path.join(v1_dir, "model.safetensors"), rng)
    store = ZLLMStore(str(tmp_path / "store"))
    store.ingest_repo(v1_dir, "orgX")

    ft1_path = str(tmp_path / "ft1" / "model.safetensors")
    _write_finetune(ft1_path, v1, rng)
    store.ingest_file(ft1_path, "u1/ft1", declared_base=key_id)
    assert store.base_map_stats["hits"] == 1 and store.base_map_stats["misses"] == 0

    # v2: unrelated weights (different scale => large bit distance, so the
    # family matcher keeps it standalone), same shapes, SAME repo/filename key
    v2_dir = str(tmp_path / "v2" / "orgX")
    v2 = _write_model(os.path.join(v2_dir, "model.safetensors"),
                      np.random.RandomState(99), scale=1.0)
    store.ingest_file(os.path.join(v2_dir, "model.safetensors"), "orgX")
    assert store.base_map_stats["invalidations"] >= 1

    ft2_path = str(tmp_path / "ft2" / "model.safetensors")
    ft2 = _write_finetune(ft2_path, v2, rng)
    res = store.ingest_file(ft2_path, "u2/ft2", declared_base=key_id)
    assert res.n_bitx > 0
    # ft2's deltas must reference v2 tensors (small deltas => strong reduction)
    assert store.retrieve_file("u2/ft2", "model.safetensors") == open(ft2_path, "rb").read()
    store.close()


def test_explicit_base_map_invalidation_rebuilds_with_one_pass(tmp_path):
    rng = np.random.RandomState(2)
    base_dir = str(tmp_path / "hub" / "org" / "b")
    base = _write_model(os.path.join(base_dir, "model.safetensors"), rng, n_tensors=5)
    store = ZLLMStore(str(tmp_path / "store"))
    store.ingest_repo(base_dir, "org/b")
    calls_after_base = store.tensor_dedup.hash_calls

    store.invalidate_base_map()
    assert store.base_map_stats["invalidations"] >= 1
    ft_dir = str(tmp_path / "hub" / "u" / "ft")
    _write_finetune(os.path.join(ft_dir, "model.safetensors"), base, rng)
    store.ingest_file(os.path.join(ft_dir, "model.safetensors"), "u/ft",
                      declared_base="org/b")
    # exactly ONE rebuild pass over the base + the fine-tune's own tensors
    assert store.tensor_dedup.hash_calls == calls_after_base + 5 + 5
    assert store.base_map_stats["misses"] == 1
    store.close()


# ---------------------------------------------------------------------------
# Index persistence (regression: tensor_dedup state used to be dropped)
# ---------------------------------------------------------------------------

def test_index_roundtrip_preserves_tensor_dedup_state(tmp_path):
    rng = np.random.RandomState(3)
    a_dir = str(tmp_path / "hub" / "org" / "a")
    a = _write_model(os.path.join(a_dir, "model.safetensors"), rng, n_tensors=5)
    s1 = ZLLMStore(str(tmp_path / "store"))
    s1.ingest_repo(a_dir, "org/a")
    s1.save_index()
    n_unique_before = s1.tensor_dedup.stats.n_unique
    index_before = dict(s1.tensor_dedup.index)
    assert n_unique_before == 5
    s1.close()

    s2 = ZLLMStore(str(tmp_path / "store"))
    assert s2.load_index()
    # regression: the dedup index + stats survive the round-trip
    assert s2.tensor_dedup.index == index_before
    assert s2.tensor_dedup.stats.n_unique == n_unique_before

    # repo with all of a's tensors plus one new one: dup detection + stats
    # continue across the restart instead of re-storing duplicates
    b = dict(a)
    b["model.extra.weight"] = (np.arange(64) / 64).astype(np.float32)
    b_dir = str(tmp_path / "hub" / "org" / "b")
    os.makedirs(b_dir, exist_ok=True)
    st.save_file(b, os.path.join(b_dir, "model.safetensors"))
    res = s2.ingest_file(os.path.join(b_dir, "model.safetensors"), "org/b")
    assert res.n_dedup == 5 and res.n_tensors == 6
    assert s2.tensor_dedup.stats.n_unique == n_unique_before + 1
    assert s2.retrieve_file("org/b", "model.safetensors") == \
        open(os.path.join(b_dir, "model.safetensors"), "rb").read()
    s2.close()


def test_index_roundtrip_preserves_primed_base_maps(tmp_path):
    """After load_index, fine-tune ingest must NOT re-hash the base (the
    primed map is persisted with the index)."""
    rng = np.random.RandomState(4)
    base_dir = str(tmp_path / "hub" / "org" / "b")
    base = _write_model(os.path.join(base_dir, "model.safetensors"), rng, n_tensors=5)
    s1 = ZLLMStore(str(tmp_path / "store"))
    s1.ingest_repo(base_dir, "org/b")
    s1.save_index()
    s1.close()

    s2 = ZLLMStore(str(tmp_path / "store"))
    assert s2.load_index()
    ft_dir = str(tmp_path / "hub" / "u" / "ft")
    _write_finetune(os.path.join(ft_dir, "model.safetensors"), base, rng)
    res = s2.ingest_file(os.path.join(ft_dir, "model.safetensors"), "u/ft",
                         declared_base="org/b")
    assert res.n_bitx > 0
    assert s2.tensor_dedup.hash_calls == 5        # the fine-tune only
    assert s2.base_map_stats["hits"] == 1 and s2.base_map_stats["misses"] == 0
    s2.close()


def test_retrieval_after_load_index_in_fresh_process(tmp_path, corpus_dir):
    root, manifest = corpus_dir
    store_root = str(tmp_path / "store")
    s1 = ZLLMStore(store_root, workers=2)
    for rid, kind in manifest[:4]:
        s1.ingest_repo(os.path.join(root, rid), rid)
    s1.save_index()
    s1.close()

    rid = manifest[1][0]  # a fine-tune (bitx records exercise dependency resolution)
    orig = open(os.path.join(root, rid, "model.safetensors"), "rb").read()
    code = (
        "import sys, hashlib\n"
        f"sys.path.insert(0, {SRC_DIR!r})\n"
        "from repro.core.pipeline import ZLLMStore\n"
        f"s = ZLLMStore({store_root!r}, workers=2)\n"
        "assert s.load_index()\n"
        f"data = s.retrieve_file({rid!r}, 'model.safetensors')\n"
        "print(hashlib.sha256(data).hexdigest())\n"
    )
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True)
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.strip() == hashlib.sha256(orig).hexdigest()


# ---------------------------------------------------------------------------
# Satellites: streaming FileDedup, codec threads arg, mmap reader
# ---------------------------------------------------------------------------

def test_filededup_streams_in_chunks(tmp_path):
    rng = np.random.RandomState(5)
    p = str(tmp_path / "big.bin")
    blob = rng.bytes(3 * 65536 + 17)  # several chunks + ragged tail
    open(p, "wb").write(blob)
    digest, size = sha256_file(p, chunk_bytes=65536)
    assert size == len(blob)
    assert digest == hashlib.sha256(blob).hexdigest()
    fd = FileDedup()
    d1, new1 = fd.scan_file(p, "a")
    d2, new2 = fd.scan_file(p, "b")
    assert d1 == d2 == digest and new1 and not new2


def test_bitx_codec_threads_arg_not_dropped():
    """Regression: BitXCodec used to accept and silently drop ``threads``."""
    codec = BitXCodec(level=3, threads=2)
    assert codec.threads == 2
    rng = np.random.RandomState(6)
    x = rng.randn(4096).astype(np.float32)
    frames, raw = codec.encode_planes(x)
    out = codec.decode_planes(frames, np.dtype("<f4"), (4096,))
    np.testing.assert_array_equal(out, x)


def test_bitx_codec_shared_across_threads_is_deterministic():
    """One codec, many threads (thread-local contexts): frames must equal
    the single-thread encoding bit for bit."""
    from concurrent.futures import ThreadPoolExecutor
    rng = np.random.RandomState(7)
    tensors = [rng.randn(8192).astype(np.float32) for _ in range(8)]
    codec = BitXCodec(level=3)
    serial = [codec.encode_planes(t) for t in tensors]
    with ThreadPoolExecutor(4) as ex:
        parallel = list(ex.map(codec.encode_planes, tensors))
    for (fs, rs), (fp, rp) in zip(serial, parallel):
        assert rs == rp and fs == fp


def test_bitx_reader_mmap_matches_bytes(tmp_path):
    rng = np.random.RandomState(8)
    base = rng.randn(500).astype(np.float32)
    ft = base + rng.randn(500).astype(np.float32) * 1e-4
    w = BitXWriter(file_metadata={"k": "v"})
    w.add_bitx("t0", "F32", (500,), base, ft, "bh", "sh")
    w.add_zipnn("t1", "F32", (500,), rng.randn(500).astype(np.float32), "sh2")
    path = str(tmp_path / "c.bitx")
    w.write(path)

    r_mm = BitXReader.open(path, use_mmap=True)
    r_by = BitXReader.open(path, use_mmap=False)
    assert r_mm.file_metadata == r_by.file_metadata
    assert [rec.to_json() for rec in r_mm.records] == [rec.to_json() for rec in r_by.records]
    for idx in range(len(r_mm.records)):
        mm_frames = [bytes(f) for f in r_mm.frames_for(idx)]
        by_frames = [bytes(f) for f in r_by.frames_for(idx)]
        assert mm_frames == by_frames
    out = r_mm.decode_tensor(0, lambda h: base, None)
    np.testing.assert_array_equal(out, ft.view(np.uint32))
    r_mm.close()  # frames may still be referenced; close must not raise
    r_by.close()


def test_reingest_same_key_same_content_is_idempotent(tmp_path):
    """Regression (found by probing): re-ingesting identical content under
    its own key must not replace the container record with a self-referencing
    file-dedup record (which sent retrieval into infinite recursion)."""
    rng = np.random.RandomState(9)
    d = str(tmp_path / "hub" / "org" / "m")
    _write_model(os.path.join(d, "model.safetensors"), rng)
    orig = open(os.path.join(d, "model.safetensors"), "rb").read()
    s = ZLLMStore(str(tmp_path / "store"))
    r1 = s.ingest_repo(d, "org/m")
    r2 = s.ingest_repo(d, "org/m")
    assert not r1[0].file_dedup_hit and r2[0].file_dedup_hit
    assert s.file_index["org/m/model.safetensors"]["kind"] == "container"
    assert s.retrieve_file("org/m", "model.safetensors") == orig
    s.close()


def test_retrieval_caches_cut_container_reads(tmp_path, corpus_dir):
    root, manifest = corpus_dir
    s = ZLLMStore(str(tmp_path / "store"), workers=2)
    for rid, kind in manifest:
        s.ingest_repo(os.path.join(root, rid), rid)
    for rid, kind in manifest:
        s.retrieve_file(rid, "model.safetensors", verify=False)
    stats = s.retrieval_cache_stats
    # dependency resolution must hit the tensor LRU (bases resolved once,
    # reused across fine-tunes) and the reader LRU (no reopen per tensor)
    assert stats["tensor_hits"] > 0
    assert stats["reader_hits"] > stats["reader_misses"]
    s.close()
