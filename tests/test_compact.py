"""Dedup-aware ``compact()`` + incremental ``gc()``.

Covers the compaction tentpole: rewriting still-referenced tensor records
(payloads, dedup targets, BitX bases) out of superseded generations into
fresh ``.compact/pool`` containers, atomic re-pinning, retirement of the
old generations, idempotence, index-v3 persistence (with v2 back-compat),
the bounded-pause incremental GC with its resumable cursor — and a
property-based churn harness that interleaves
ingest/re-register/delete/gc/compact randomly and holds every live file
byte-identical to a shadow dict-of-bytes oracle throughout.
"""

import json
import os
import shutil
import tempfile

import numpy as np
import pytest

from hypothesis import given, settings, strategies as stt
from repro.core.lifecycle import make_vid
from repro.core.pipeline import COMPACT_KEY, ZLLMStore
from repro.formats import safetensors as st

N_TENSORS = 6
N_ELEMS = 512


def _write(path, tensors):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    st.save_file(tensors, path)


def _fresh_tensors(seed, n_tensors=N_TENSORS, n=N_ELEMS):
    rng = np.random.RandomState(seed)
    return {f"t{i}": rng.randn(n).astype(np.float32) for i in range(n_tensors)}


def _read(path):
    with open(path, "rb") as f:
        return f.read()


def _chain_store(tmp_path, rounds=3):
    """Base ingested, then ``rounds`` partial re-registers that each replace
    a rotating third of the tensors with fresh random content (large bit
    distance, so the new generations store standalone and *dedup* the
    unchanged tensors against pins in older generations — the chain that
    leaves dead payloads gc cannot reclaim). Returns
    (store, final file bytes, per-gen paths)."""
    store = ZLLMStore(str(tmp_path / "store"))
    cur = _fresh_tensors(0)
    paths = []
    p = str(tmp_path / "hub" / "g0" / "model.safetensors")
    _write(p, cur)
    paths.append(p)
    store.ingest_file(p, "org/b")
    for r in range(rounds):
        for i in range(N_TENSORS):
            if i % rounds == r:
                cur[f"t{i}"] = np.random.RandomState(1000 + 10 * r + i).randn(
                    N_ELEMS).astype(np.float32)
        p = str(tmp_path / "hub" / f"g{r + 1}" / "model.safetensors")
        _write(p, dict(cur))
        res = store.ingest_file(p, "org/b")
        assert res.n_dedup > 0, "setup: chain must dedup unchanged tensors"
        paths.append(p)
    assert store.file_index["org/b/model.safetensors"]["gen"] == rounds
    return store, _read(paths[-1]), paths


# ---------------------------------------------------------------------------
# compact(): reclaim, re-pin, bit-identity, idempotence
# ---------------------------------------------------------------------------

def test_compact_reclaims_dedup_chain_and_preserves_bytes(tmp_path):
    """THE acceptance scenario: after a re-register chain, the superseded
    generations are pinned by later generations' dedup records but mostly
    dead. compact() must move exactly the still-referenced payloads into a
    fresh container, retire every superseded generation, reclaim >= 30% of
    the superseded bytes net, and keep the live file bit-identical."""
    store, final, _ = _chain_store(tmp_path)
    assert store.gc()["collected"] == 0  # the chain pins everything
    superseded = store.summary()["lifecycle"]["superseded_bytes"]
    assert superseded > 0

    rep = store.compact()
    assert rep["retired_versions"] == rep["superseded_versions"] == 3
    assert rep["moved_records"] > 0
    assert rep["container"] == make_vid(COMPACT_KEY, 0)
    assert rep["reclaimed_bytes"] == superseded
    assert rep["net_reclaimed_bytes"] >= 0.3 * superseded  # the ISSUE bar
    assert store.stats.compaction_reclaimed_bytes == rep["net_reclaimed_bytes"]
    assert store.stats.compact_runs == 1

    # moved hashes now pin into the compact pool, old gens are gone
    pool_pins = [loc for loc in store.tensor_locations.values()
                 if loc[0] == COMPACT_KEY]
    assert len(pool_pins) == rep["moved_records"]
    for g in range(3):
        assert not store.lifecycle.exists("org/b/model.safetensors", g)
        assert not os.path.exists(
            store._container_path("org/b/model.safetensors", g))

    # equivalence proof: the live file decodes bit-identically through the
    # pool, and fsck validates every post-compact pin
    assert store.retrieve_file("org/b", "model.safetensors") == final
    assert store.fsck(spot_check=None).ok
    store.close()


def test_compact_is_idempotent_on_its_own_pool(tmp_path):
    """A second compact() must not rewrite the pool it just wrote: the pool
    container is pure payload and fully needed, so it is skipped."""
    store, final, _ = _chain_store(tmp_path)
    store.compact()
    rep2 = store.compact()
    assert rep2["moved_records"] == 0 and rep2["retired_versions"] == 0
    assert rep2["skipped_versions"] == 1  # the pool itself
    assert store.lifecycle.exists(COMPACT_KEY, 0)
    assert store.retrieve_file("org/b", "model.safetensors") == final
    assert store.fsck(spot_check=None).ok
    store.close()


def test_compact_skips_fully_needed_base_generation(tmp_path):
    """A superseded base whose EVERY payload is still a live fine-tune's
    BitX base is pure relocation — compact must leave it in place (zero
    churn), and the fine-tune keeps decoding against it."""
    base = _fresh_tensors(1)
    bp = str(tmp_path / "hub" / "b" / "model.safetensors")
    fp = str(tmp_path / "hub" / "f" / "model.safetensors")
    _write(bp, base)
    rng = np.random.RandomState(2)
    _write(fp, {k: v + rng.randn(*v.shape).astype(np.float32) * 1e-3
                for k, v in base.items()})
    store = ZLLMStore(str(tmp_path / "store"))
    store.ingest_file(bp, "org/b")
    res = store.ingest_file(fp, "u/f", declared_base="org/b/model.safetensors")
    assert res.n_bitx == N_TENSORS
    # supersede the base with unrelated content (standalone)
    v2 = str(tmp_path / "hub" / "v2" / "model.safetensors")
    _write(v2, _fresh_tensors(99))
    store.ingest_file(v2, "org/b")

    rep = store.compact()
    assert rep["superseded_versions"] == 1
    assert rep["skipped_versions"] == 1 and rep["retired_versions"] == 0
    assert rep["moved_records"] == 0 and rep["container"] is None
    assert store.lifecycle.exists("org/b/model.safetensors", 0)
    assert store.retrieve_file("u/f", "model.safetensors") == _read(fp)
    assert store.fsck(spot_check=None).ok
    store.close()


def test_compact_moves_bitx_bases_of_live_finetunes(tmp_path):
    """A superseded base that is only PARTIALLY referenced (the fine-tune
    covers a subset of its tensors) must be compacted: the referenced base
    payloads move into the pool, the generation retires, and the
    fine-tune's BitX records decode through the pool bit-identically."""
    base = _fresh_tensors(3, n_tensors=6)
    bp = str(tmp_path / "hub" / "b" / "model.safetensors")
    _write(bp, base)
    # fine-tune only carries 3 of the 6 base tensors
    rng = np.random.RandomState(4)
    ft = {k: base[k] + rng.randn(N_ELEMS).astype(np.float32) * 1e-3
          for k in ("t0", "t1", "t2")}
    fp = str(tmp_path / "hub" / "f" / "model.safetensors")
    _write(fp, ft)
    store = ZLLMStore(str(tmp_path / "store"))
    store.ingest_file(bp, "org/b")
    res = store.ingest_file(fp, "u/f", declared_base="org/b/model.safetensors")
    assert res.n_bitx == 3
    v2 = str(tmp_path / "hub" / "v2" / "model.safetensors")
    _write(v2, _fresh_tensors(77))
    store.ingest_file(v2, "org/b")

    rep = store.compact()
    assert rep["retired_versions"] == 1 and rep["moved_records"] == 3
    assert not store.lifecycle.exists("org/b/model.safetensors", 0)
    # the moved records are the fine-tune's bases, pinned into the pool
    for k in ("t0", "t1", "t2"):
        # resolve via decode: bit-identical through the pool
        data, meta = store.retrieve_tensor("u/f", "model.safetensors", k)
        assert data == ft[k].tobytes() and meta["codec"] == "bitx"
    assert store.retrieve_file("u/f", "model.safetensors") == _read(fp)
    assert store.fsck(spot_check=None).ok
    store.close()


def test_compact_noop_when_nothing_superseded(tmp_path):
    p = str(tmp_path / "hub" / "m" / "model.safetensors")
    _write(p, _fresh_tensors(5))
    store = ZLLMStore(str(tmp_path / "store"))
    store.ingest_file(p, "org/m")
    rep = store.compact()
    assert rep == {**rep, "superseded_versions": 0, "moved_records": 0,
                   "retired_versions": 0, "container": None}
    assert store.stats.compact_runs == 0  # a no-op is not a run
    assert store.retrieve_file("org/m", "model.safetensors") == _read(p)
    store.close()


def test_compact_retires_unreachable_garbage_without_container(tmp_path):
    """Unreachable versions (deleted, never gc'd) are retired by compact
    directly — no pool container is written for them."""
    p = str(tmp_path / "hub" / "m" / "model.safetensors")
    _write(p, _fresh_tensors(6))
    store = ZLLMStore(str(tmp_path / "store"))
    store.ingest_file(p, "org/m")
    cpath = store.file_index["org/m/model.safetensors"]["path"]
    store.delete_repo("org/m")
    rep = store.compact()
    assert rep["retired_versions"] == 1 and rep["container"] is None
    assert not os.path.exists(cpath)
    assert store.lifecycle.versions == {}
    assert store.fsck(spot_check=None).ok
    store.close()


def test_compact_never_touches_file_dedup_anchored_generations(tmp_path):
    """A whole-file-dedup alias pins the generation serving its bytes; that
    generation is an anchor, so compact must neither move nor retire it
    even after the original key is re-registered."""
    p = str(tmp_path / "hub" / "m" / "model.safetensors")
    _write(p, _fresh_tensors(7))
    store = ZLLMStore(str(tmp_path / "store"))
    store.ingest_file(p, "org/m")
    cp = str(tmp_path / "hub" / "copy" / "model.safetensors")
    os.makedirs(os.path.dirname(cp), exist_ok=True)
    shutil.copyfile(p, cp)
    assert store.ingest_file(cp, "mirror/m").file_dedup_hit
    v2 = str(tmp_path / "hub" / "v2" / "model.safetensors")
    _write(v2, _fresh_tensors(88))
    store.ingest_file(v2, "org/m")  # original superseded at the key level

    rep = store.compact()
    assert rep["superseded_versions"] == 0  # alias anchors gen 0
    assert store.lifecycle.exists("org/m/model.safetensors", 0)
    assert store.retrieve_file("mirror/m", "model.safetensors") == _read(p)
    assert store.retrieve_file("org/m", "model.safetensors") == _read(v2)
    assert store.fsck(spot_check=None).ok
    store.close()


def test_compact_pool_collected_when_last_dependant_dies(tmp_path):
    """The pool container is an ordinary version: once nothing references
    its records, gc reclaims it (and scrubs its pins)."""
    store, final, _ = _chain_store(tmp_path)
    store.compact()
    assert store.lifecycle.exists(COMPACT_KEY, 0)
    store.delete_repo("org/b")
    swept = store.gc()
    assert swept["collected"] == 2  # the live gen + the pool
    assert not store.lifecycle.exists(COMPACT_KEY, 0)
    assert not any(k == COMPACT_KEY for k, _, _ in store.tensor_locations.values())
    assert store.fsck(spot_check=None).ok
    store.close()


def test_compact_survives_index_roundtrip(tmp_path):
    """compact() persists the index itself (persist-then-unlink): a fresh
    process loads the post-compact state and serves bit-identically."""
    store, final, _ = _chain_store(tmp_path)
    store.compact()  # persist=True by default
    store.close()
    with ZLLMStore(str(tmp_path / "store")) as s2:
        assert s2.load_index()
        assert s2.lifecycle.exists(COMPACT_KEY, 0)
        assert s2.stats.compact_runs == 1
        assert s2.retrieve_file("org/b", "model.safetensors") == final
        assert s2.fsck(spot_check=None).ok


# ---------------------------------------------------------------------------
# incremental gc: bounded steps, resumable cursor, index v3
# ---------------------------------------------------------------------------

def _garbage_store(tmp_path, n=5):
    store = ZLLMStore(str(tmp_path / "store"))
    for i in range(n):
        p = str(tmp_path / "hub" / f"m{i}" / "model.safetensors")
        _write(p, _fresh_tensors(100 + i, n_tensors=2, n=128))
        store.ingest_file(p, f"org/m{i}")
    keep = str(tmp_path / "hub" / "keep" / "model.safetensors")
    _write(keep, _fresh_tensors(999, n_tensors=2, n=128))
    store.ingest_file(keep, "org/keep")
    for i in range(n):
        store.delete_repo(f"org/m{i}")
    return store, keep


def test_incremental_gc_matches_full_sweep(tmp_path):
    """With a near-zero pause budget every step retires exactly one
    version; the aggregate must equal what a stop-the-world sweep would
    reclaim, the pause metric must be recorded, and survivors stay
    bit-exact."""
    store, keep = _garbage_store(tmp_path, n=5)
    agg = store.gc(incremental=True, max_pause_ms=0.0, persist=False)
    assert agg["collected"] == 5
    assert agg["steps"] >= 5  # one victim per zero-budget step (+ final empty)
    assert agg["max_pause_ms"] > 0
    assert store.stats.gc_max_pause_ms >= agg["max_pause_ms"]
    assert store._gc_cursor == ""  # completed sweep resets the cursor
    assert store.gc()["collected"] == 0  # nothing left for stop-the-world
    assert store.retrieve_file("org/keep", "model.safetensors") == _read(keep)
    assert store.fsck(spot_check=None).ok
    store.close()


def test_incremental_gc_cursor_resumes_across_reload(tmp_path):
    """A single bounded step persists its cursor in the v3 index; a fresh
    process resumes the sweep where the last one stopped."""
    store, keep = _garbage_store(tmp_path, n=4)
    step = store.gc_step(max_pause_ms=0.0, persist=True)
    assert step["collected"] == 1 and step["remaining"] == 3
    cursor = store._gc_cursor
    assert cursor
    store.close()

    with ZLLMStore(str(tmp_path / "store")) as s2:
        assert s2.load_index()
        assert s2._gc_cursor == cursor
        agg = s2.gc(incremental=True, max_pause_ms=1000.0)
        assert agg["collected"] == 3
        assert s2._gc_cursor == ""
        assert s2.retrieve_file("org/keep", "model.safetensors") == _read(keep)
        assert s2.fsck(spot_check=None).ok


def test_incremental_gc_interleaves_with_ingest(tmp_path):
    """The admin lock is released between steps: an ingest issued after a
    step (here: sequentially, between manual steps) lands normally and the
    next step's re-mark sees it as an anchor."""
    store, keep = _garbage_store(tmp_path, n=3)
    assert store.gc_step(max_pause_ms=0.0, persist=False)["collected"] == 1
    mid = str(tmp_path / "hub" / "mid" / "model.safetensors")
    _write(mid, _fresh_tensors(555, n_tensors=2, n=128))
    store.ingest_file(mid, "org/mid")  # between steps
    while not store.gc_step(max_pause_ms=0.0, persist=False)["done"]:
        pass
    assert store.retrieve_file("org/mid", "model.safetensors") == _read(mid)
    assert store.retrieve_file("org/keep", "model.safetensors") == _read(keep)
    assert store.fsck(spot_check=None).ok
    store.close()


def test_index_v2_backward_compat_load(tmp_path):
    """A v2 index (PR-2/3 era: no gc_cursor, no compaction stats) must load
    with the new fields defaulted and churn working immediately."""
    store, final, _ = _chain_store(tmp_path)
    idx_path = store.save_index()
    store.close()

    idx = json.load(open(idx_path))
    assert idx["format"] == 4
    idx["format"] = 2
    del idx["gc_cursor"]
    idx["lifecycle"].pop("tombstones", None)  # v4-only key
    for k in ("compaction_reclaimed_bytes", "compact_runs", "gc_max_pause_ms",
              "auto_compact_runs"):
        idx["stats"].pop(k, None)
    with open(idx_path, "w") as f:
        json.dump(idx, f)

    with ZLLMStore(str(tmp_path / "store")) as s2:
        assert s2.load_index()
        assert s2._gc_cursor == "" and s2.stats.compact_runs == 0
        assert s2.retrieve_file("org/b", "model.safetensors") == final
        rep = s2.compact()  # compaction works on the upgraded store
        assert rep["retired_versions"] == 3
        assert s2.retrieve_file("org/b", "model.safetensors") == final
        assert s2.fsck(spot_check=None).ok


# ---------------------------------------------------------------------------
# Property-based churn: random interleavings vs a shadow oracle
# ---------------------------------------------------------------------------

_P_TENSORS = 3
_P_ELEMS = 64


def _churn(ops, root):
    """Drive one random churn sequence. The oracle is a dict of raw file
    bytes per live repo; every operation must keep each live file
    retrieving byte-identically, and the store must finish fsck-clean and
    reload-clean."""
    rids = ["r0", "r1", "r2", "r3"]
    store = ZLLMStore(os.path.join(root, "store"))
    oracle = {}
    content = {}
    seq = 0
    try:
        for op in ops:
            rid = rids[op % len(rids)]
            kind = (op // len(rids)) % 6
            if kind == 0 or (kind == 1 and rid not in content):
                # fresh ingest (new random content)
                tensors = {f"t{i}": np.random.RandomState(op * 7 + i).randn(
                    _P_ELEMS).astype(np.float32) for i in range(_P_TENSORS)}
            elif kind == 1:
                # partial re-register: flip a drawn subset of tensors
                tensors = dict(content[rid])
                for i in range(_P_TENSORS):
                    if (op >> (4 + i)) & 1:
                        tensors[f"t{i}"] = np.random.RandomState(
                            op * 13 + i).randn(_P_ELEMS).astype(np.float32)
            elif kind == 2:
                # duplicate upload: another live repo's exact bytes
                src = next((r for r in rids if r in oracle and r != rid), None)
                if src is None:
                    continue
                seq += 1
                p = os.path.join(root, "hub", f"u{seq}", "model.safetensors")
                os.makedirs(os.path.dirname(p), exist_ok=True)
                with open(p, "wb") as f:
                    f.write(oracle[src])
                store.ingest_file(p, rid)
                oracle[rid] = oracle[src]
                content[rid] = dict(content[src])
                continue
            elif kind == 3:
                if rid in oracle:
                    store.delete_repo(rid)
                    del oracle[rid], content[rid]
                continue
            elif kind == 4:
                if op % 2:
                    store.gc()
                else:
                    store.gc(incremental=True, max_pause_ms=0.5, persist=False)
                continue
            else:
                store.compact(persist=False)
                continue
            seq += 1
            p = os.path.join(root, "hub", f"u{seq}", "model.safetensors")
            _write(p, tensors)
            store.ingest_file(p, rid)
            content[rid] = tensors
            oracle[rid] = _read(p)
            # spot-check one live repo after every mutating op
            probe = sorted(oracle)[op % len(oracle)]
            assert store.retrieve_file(probe, "model.safetensors") == oracle[probe]
        # the full invariant: every live file bit-identical, store clean
        for rid, data in oracle.items():
            assert store.retrieve_file(rid, "model.safetensors") == data
        report = store.fsck(spot_check=None)
        assert report.ok, (report.dangling, report.corrupt)
        store.save_index()
    finally:
        store.close()
    with ZLLMStore(os.path.join(root, "store")) as s2:
        assert s2.load_index()
        for rid, data in oracle.items():
            assert s2.retrieve_file(rid, "model.safetensors") == data
        assert s2.fsck(spot_check=None).ok


@settings(deadline=None, max_examples=10)
@given(stt.lists(stt.integers(0, 2 ** 20), min_size=6, max_size=24))
def test_property_random_churn_matches_shadow_oracle(ops):
    root = tempfile.mkdtemp(prefix="zllm-compact-prop-")
    try:
        _churn(ops, root)
    finally:
        shutil.rmtree(root, ignore_errors=True)
