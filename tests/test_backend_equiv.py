"""Backend equivalence sweep: the batched jax/Pallas path (interpret mode on
CPU) must be bit-identical to the numpy host path — per-op across dtypes ×
odd/padded shapes × bucket sizes, and end-to-end at the store level (same
corpus, same bytes on disk). This is the workers-1-vs-4 determinism machinery
extended along the backend axis: since containers are pure functions of
(bytes, level, threads, backend-semantics), proving the array transforms
bit-identical proves the containers are too."""

import hashlib
import os

import ml_dtypes
import numpy as np
import pytest

from repro.core import bitx
from repro.core.bitx import JaxBackend, NumpyBackend, get_backend
from repro.core.pipeline import ZLLMStore

NP = NumpyBackend()

# dtypes the sweep covers: bf16 rides its u16 bit view (exactly how the
# pipeline stores BF16 tensors), fp32 is the common standalone case, int8
# exercises the kernel-unsupported-kind path (host bit-view conversion
# before launch), fp64 exercises the 8-byte host fallback (jax x64 off).
DTYPES = ["uint16", "float32", "int8", "float64"]

# odd / padded / tiny / multi-dim shapes: 1 element, non-multiples of the
# 1024-lane kernel tiling, one exact multiple, and a 2-D tensor
SHAPES = [(1,), (3,), (37, 5), (1023,), (1024,), (1025,), (4096,)]

BUCKETS = [1, 2, 5]


def _mk(dtype, shape, seed):
    rng = np.random.default_rng(seed)
    if np.dtype(dtype).kind in "ui":
        info = np.iinfo(dtype)
        return rng.integers(info.min, info.max, shape, dtype)
    return rng.random(shape).astype(dtype)


def _assert_plane_lists_equal(a, b):
    assert len(a) == len(b)
    for g1, g2 in zip(a, b):
        assert len(g1) == len(g2)
        for p1, p2 in zip(g1, g2):
            assert p1.dtype == p2.dtype and (p1 == p2).all()


@pytest.fixture(scope="module")
def jx():
    return get_backend("jax")


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("shape", SHAPES)
def test_single_op_equivalence(jx, dtype, shape):
    x = _mk(dtype, shape, 11)
    base = _mk(dtype, shape, 12)
    # zipnn split/merge
    p_np, p_jx = NP.byte_planes(x), jx.byte_planes(x)
    _assert_plane_lists_equal([p_np], [p_jx])
    m_np = NP.merge_planes(p_np, np.dtype(dtype), shape)
    m_jx = jx.merge_planes(p_np, np.dtype(dtype), shape)
    assert m_np.dtype == m_jx.dtype and m_np.shape == m_jx.shape
    assert (m_np == m_jx).all() and (m_np == x).all()
    # bitx xor/merge
    d_np = NP.xor_delta_planes(base.reshape(-1), x.reshape(-1))
    d_jx = jx.xor_delta_planes(base.reshape(-1), x.reshape(-1))
    _assert_plane_lists_equal([d_np], [d_jx])
    r_np = NP.merge_planes_xor(d_np, base.reshape(-1))
    r_jx = jx.merge_planes_xor(d_np, base.reshape(-1))
    assert r_np.dtype == r_jx.dtype and (r_np == r_jx).all()


@pytest.mark.parametrize("bucket", BUCKETS)
def test_batched_ops_equal_mapped_singles(jx, bucket):
    """One fused launch over a concatenated bucket must slice back to exactly
    the per-tensor results — across mixed dtypes in one batch, so the
    dtype-grouping logic is exercised too."""
    xs, pairs = [], []
    seed = 0
    for dtype in DTYPES:
        for shape in SHAPES[:bucket + 2]:
            seed += 2
            x, b = _mk(dtype, shape, seed), _mk(dtype, shape, seed + 1)
            xs.append(x)
            pairs.append((b.reshape(-1), x.reshape(-1)))
    xs, pairs = xs[: bucket * 4], pairs[: bucket * 4]
    _assert_plane_lists_equal(jx.byte_planes_batch(xs),
                              [NP.byte_planes(x) for x in xs])
    d_batch = jx.xor_delta_planes_batch(pairs)
    d_ref = [NP.xor_delta_planes(b, f) for b, f in pairs]
    _assert_plane_lists_equal(d_batch, d_ref)
    m_batch = jx.merge_planes_xor_batch([(d, b) for d, (b, _) in zip(d_ref, pairs)])
    m_ref = [NP.merge_planes_xor(d, b) for d, (b, _) in zip(d_ref, pairs)]
    for a, b in zip(m_batch, m_ref):
        assert a.dtype == b.dtype and (a == b).all()
    z_items = [(NP.byte_planes(x), x.dtype, x.shape) for x in xs]
    z_batch = jx.merge_planes_batch(z_items)
    for got, x in zip(z_batch, xs):
        assert got.dtype == x.dtype and got.shape == x.shape and (got == x).all()


def test_roundtrip_through_jax_recovers_exact_bits(jx):
    """Full encode→decode on the jax path alone is the identity on bits."""
    for dtype in DTYPES:
        x = _mk(dtype, (777,), 31)
        base = _mk(dtype, (777,), 32)
        planes = jx.xor_delta_planes(base, x)
        back = jx.merge_planes_xor(planes, base)
        assert bytes(back.tobytes()) == x.tobytes()
        split = jx.byte_planes(x)
        merged = jx.merge_planes(split, np.dtype(dtype), (777,))
        assert merged.tobytes() == x.tobytes()


# ---------------------------------------------------------------------------
# Keyed encode items: bases resident on the device, keyed by content hash
# ---------------------------------------------------------------------------

# each keyed case's dtypes; bf16 rides its u16 bit view, as the pipeline
# reads BF16 tensors
KEYED = {"bfloat16": ["bfloat16"], "float16": ["float16"],
         "float32": ["float32"], "uint8": ["uint8"],
         "mixed": ["bfloat16", "float32", "uint8", "float16"]}


def _mk_keyed(dtype, shape, seed):
    if dtype == "bfloat16":
        rng = np.random.default_rng(seed)
        return rng.random(shape).astype(ml_dtypes.bfloat16).view(np.uint16)
    return _mk(dtype, shape, seed)


def _once(arr):
    """A base loader that may run once: a hit must not read the base."""
    calls = []

    def load():
        assert not calls, "base loaded again on a resident hit"
        calls.append(1)
        return arr
    return load


def _keyed_items(dtypes, seed, shapes=((37, 5), (1025,)), family="fam"):
    """Keyed items of one family, the plain pairs of the same tensors, and
    base bytes."""
    items, pairs = [], []
    for dtype in dtypes:
        for shape in shapes:
            seed += 2
            base, ft = _mk_keyed(dtype, shape, seed), _mk_keyed(dtype, shape, seed + 1)
            h = hashlib.sha256(base.tobytes()).hexdigest()
            items.append((_once(base), ft.reshape(-1), h, family))
            pairs.append((base.reshape(-1), ft.reshape(-1)))
    return items, pairs, sum(b.nbytes for b, _ in pairs)


@pytest.mark.parametrize("case", sorted(KEYED))
def test_keyed_items_equal_numpy_miss_then_hit(case):
    """Keyed items give the numpy planes twice: first as misses that put
    each base on the device, then as hits that never load it again."""
    jb = JaxBackend()
    items, pairs, nbytes = _keyed_items(KEYED[case], 100)
    ref = [NP.xor_delta_planes(b, f) for b, f in pairs]
    _assert_plane_lists_equal(jb.xor_delta_planes_batch(items), ref)
    c = jb.path_counts()
    assert (c["resident_miss_bytes"], c["resident_hit_bytes"]) == (nbytes, 0)
    assert c["resident_bytes"] == nbytes and c["device_bytes"] == nbytes
    _assert_plane_lists_equal(jb.xor_delta_planes_batch(items), ref)
    c = jb.path_counts()
    assert (c["resident_miss_bytes"], c["resident_hit_bytes"]) == (nbytes, nbytes)
    assert c["resident_bytes"] == nbytes and c["resident_evictions"] == 0


def test_keyed_item_with_new_bytes_misses():
    """The key is the base's content: the same tensor with other bytes
    misses, and its planes are made against the new bytes."""
    jb = JaxBackend()
    ft = _mk("float32", (1025,), 1)
    for seed in (2, 3):
        base = _mk("float32", (1025,), seed)
        h = hashlib.sha256(base.tobytes()).hexdigest()
        got = jb.xor_delta_planes_batch([(_once(base), ft, h, "fam")])
        _assert_plane_lists_equal(got, [NP.xor_delta_planes(base, ft)])
    c = jb.path_counts()
    assert c["resident_miss_bytes"] == 2 * ft.nbytes and c["resident_hit_bytes"] == 0


def test_keyed_bases_evicted_least_recently_used(monkeypatch):
    """Past the budget a miss evicts the least recently used base of
    another family; a hit refreshes."""
    items, pairs, _ = _keyed_items(["float32"], 200, shapes=((1025,),) * 3)
    one = pairs[0][0].nbytes
    monkeypatch.setattr(bitx, "_resident_budget_bytes", lambda: 2 * one + one // 2)
    jb = JaxBackend()
    a, b, c = [(load, ft, h, fam) for (load, ft, h, _), fam
               in zip(items, ("f1", "f2", "f3"))]
    jb.xor_delta_planes_batch([a, b])
    jb.xor_delta_planes_batch([a])          # hit: a is now the most recent
    jb.xor_delta_planes_batch([c])          # evicts b
    got = jb.path_counts()
    assert got["resident_evictions"] == 1 and got["resident_bytes"] == 2 * one
    assert got["resident_hit_bytes"] == one
    jb.xor_delta_planes_batch([(_once(pairs[1][0]),) + b[1:]])  # evicts a
    got = jb.path_counts()
    assert got["resident_miss_bytes"] == 4 * one and got["resident_evictions"] == 2
    out = jb.xor_delta_planes_batch([c, b])  # both held: no loader runs
    _assert_plane_lists_equal(out, [NP.xor_delta_planes(*pairs[2]),
                                    NP.xor_delta_planes(*pairs[1])])


def test_keyed_family_larger_than_budget_keeps_its_first_bases(monkeypatch):
    """A family whose base exceeds the budget never evicts its own: read in
    the same order every time, the bases it put there first keep hitting
    and the rest are sent for each use, with the numpy planes throughout."""
    items, pairs, _ = _keyed_items(["float16"], 250, shapes=((1025,),) * 4)
    one = pairs[0][0].nbytes
    monkeypatch.setattr(bitx, "_resident_budget_bytes", lambda: 2 * one + one // 2)
    ref = [NP.xor_delta_planes(b, f) for b, f in pairs]
    jb = JaxBackend()
    for _ in range(3):
        fresh = [(lambda j=j: pairs[j][0],) + items[j][1:] for j in range(4)]
        _assert_plane_lists_equal(jb.xor_delta_planes_batch(fresh), ref)
    got = jb.path_counts()
    assert got["resident_bytes"] == 2 * one and got["resident_evictions"] == 0
    assert got["resident_hit_bytes"] == 2 * 2 * one
    assert got["resident_miss_bytes"] == (4 + 2 + 2) * one
    # another family still takes the room it needs from this one
    other, other_pairs, _ = _keyed_items(["float16"], 260, shapes=((1025,),),
                                         family="other")
    _assert_plane_lists_equal(jb.xor_delta_planes_batch(other),
                              [NP.xor_delta_planes(*other_pairs[0])])
    got = jb.path_counts()
    assert got["resident_evictions"] == 1 and got["resident_bytes"] == 2 * one


def test_keyed_bases_under_threads(monkeypatch):
    """Threads sharing one backend under a budget of two bases, over two
    families of two: no count is lost, the bytes held match the views held
    and stay in budget, and every result is the numpy one."""
    import sys
    import threading
    items, pairs, _ = _keyed_items(["uint16"], 300, shapes=((1025,),) * 4)
    one = pairs[0][0].nbytes
    monkeypatch.setattr(bitx, "_resident_budget_bytes", lambda: 2 * one)
    ref = [NP.xor_delta_planes(b, f) for b, f in pairs]
    jb, errors, rounds = JaxBackend(), [], 6

    def work(k):
        try:
            for r in range(rounds):
                j = (k + r) % len(items)
                item = (lambda j=j: pairs[j][0], items[j][1], items[j][2], j % 2)
                _assert_plane_lists_equal(jb.xor_delta_planes_batch([item]), [ref[j]])
        except Exception as e:  # surfaced by the assert below
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work, args=(k,)) for k in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads) and not errors, errors
    c = jb.path_counts()
    assert c["resident_hit_bytes"] + c["resident_miss_bytes"] == 16 * rounds * one
    assert c["resident_bytes"] == sum(a.nbytes for a, _ in jb._resident.values())
    assert c["resident_bytes"] == sum(jb._family_bytes.values())
    assert c["resident_bytes"] <= 2 * one and c["device_tensors"] == 16 * rounds


# ---------------------------------------------------------------------------
# Store level: same corpus, same bytes on disk
# ---------------------------------------------------------------------------

def _container_bytes(store_root):
    out = {}
    croot = os.path.join(store_root, "containers")
    for dirpath, _, files in os.walk(croot):
        for fn in files:
            p = os.path.join(dirpath, fn)
            out[os.path.relpath(p, croot)] = open(p, "rb").read()
    return out


def test_store_containers_bit_identical_numpy_vs_jax(tmp_path, corpus_dir):
    """The acceptance-criterion test: ``backend="jax"`` (batched device
    encode, parallel workers) writes byte-identical containers to
    ``backend="numpy"`` (serial reference) over the shared corpus, and both
    retrieve bit-exactly."""
    root, manifest = corpus_dir
    stores, hits = {}, {}
    for name, kw in (("numpy", dict(workers=0, backend="numpy")),
                     ("jax", dict(workers=4, backend="jax"))):
        s = ZLLMStore(str(tmp_path / name), **kw)
        for rid, kind in manifest:
            before = s.backend.path_counts()["resident_hit_bytes"]
            s.ingest_repo(os.path.join(root, rid), rid)
            hits[name, rid] = s.backend.path_counts()["resident_hit_bytes"] - before
        stores[name] = s
    # the second fine-tune of each family encodes on its resident base
    seconds = [rid for rid, kind in manifest
               if kind == "finetune" and rid.split("/")[1].endswith("-1")]
    assert seconds and all(hits["jax", rid] > 0 for rid in seconds), hits
    assert not any(v for (name, _), v in hits.items() if name == "numpy")
    assert stores["numpy"].summary()["array_backend"] == "numpy"
    assert stores["jax"].summary()["array_backend"] == "jax"

    c_np = _container_bytes(str(tmp_path / "numpy"))
    c_jx = _container_bytes(str(tmp_path / "jax"))
    assert c_np.keys() == c_jx.keys() and len(c_np) > 0
    for name in c_np:
        assert c_np[name] == c_jx[name], f"container diverged across backends: {name}"

    for rid, kind in manifest:
        orig = open(os.path.join(root, rid, "model.safetensors"), "rb").read()
        assert stores["jax"].retrieve_file(rid, "model.safetensors") == orig
    for s in stores.values():
        s.close()


def test_grouped_container_decode_matches_source(tmp_path, corpus_dir, monkeypatch):
    """With a decode bound below one tensor, every bitx/zipnn record merges
    in its own device launch, and retrieval still returns the source bytes."""
    from repro.core import pipeline as pipeline_mod
    monkeypatch.setattr(pipeline_mod, "_DEVICE_BATCH_MAX_BYTES", 1)
    root, manifest = corpus_dir
    s = ZLLMStore(str(tmp_path / "jax"), workers=4, backend="jax")
    for rid, _ in manifest[:3]:
        s.ingest_repo(os.path.join(root, rid), rid)
    for rid, _ in manifest[:3]:
        orig = open(os.path.join(root, rid, "model.safetensors"), "rb").read()
        assert s.retrieve_file(rid, "model.safetensors") == orig
    assert s.summary()["codec_bytes"].get("bitx", 0) > 0
    s.close()


@pytest.mark.parametrize("release", ["close", "invalidate_base_map"])
def test_resident_bases_released(tmp_path, corpus_dir, release):
    """A store's resident bases go with ``close()`` and with
    ``invalidate_base_map``; the next fine-tune puts its base back."""
    root, manifest = corpus_dir
    jb = JaxBackend()
    s = ZLLMStore(str(tmp_path / "jax"), workers=2, backend=jb)
    fam0 = ["org0/base-model-0", "user0-0/ft-0-0", "user0-1/ft-0-1"]
    assert set(fam0) <= {rid for rid, _ in manifest}
    for rid in fam0[:2]:
        s.ingest_repo(os.path.join(root, rid), rid)
    held = jb.path_counts()["resident_bytes"]
    assert held > 0 and jb.path_counts()["resident_miss_bytes"] == held
    if release == "invalidate_base_map":
        s.invalidate_base_map()
        assert jb.path_counts()["resident_bytes"] == 0
        s.ingest_repo(os.path.join(root, fam0[2]), fam0[2])
        assert jb.path_counts()["resident_miss_bytes"] > held
    s.close()
    assert jb.path_counts()["resident_bytes"] == 0


def test_get_backend_resolution():
    assert get_backend("numpy").name == "numpy"
    assert get_backend("jax").name == "jax"
    # auto picks jax only on a TPU host; elsewhere the numpy host path
    # (interpret-mode kernels are Python emulation)
    import jax
    expected = "jax" if jax.default_backend() == "tpu" else "numpy"
    assert get_backend("auto").name == expected
    # instances pass through, unknown names fail loudly
    nb = NumpyBackend()
    assert get_backend(nb) is nb
    with pytest.raises(ValueError, match="torch"):
        get_backend("torch")


def test_path_counts_same_keys_on_both_backends():
    """``array_path`` has one shape on every backend: numpy counts every
    tensor as host path; jax counts device kernels, and host for 8-byte
    words without x64."""
    from repro.core.bitx import JaxBackend
    xs = [_mk(np.uint16, (777,), 41), _mk(np.float64, (33,), 42)]
    nb, jb = NumpyBackend(), JaxBackend()
    nb.byte_planes_batch(xs)
    jb.byte_planes_batch(xs)
    resident = {"resident_hit_bytes": 0, "resident_miss_bytes": 0,
                "resident_bytes": 0, "resident_evictions": 0}
    assert nb.path_counts() == {"device_tensors": 0, "device_bytes": 0,
                                "host_tensors": 2, "host_bytes": 777 * 2 + 33 * 8,
                                **resident}
    assert jb.path_counts() == {"device_tensors": 1, "device_bytes": 777 * 2,
                                "host_tensors": 1, "host_bytes": 33 * 8, **resident}
    # keyed encode items: numpy holds nothing; jax counts the base it puts
    # there, and holds no unkeyed base
    base, ft = _mk(np.float32, (33,), 43), _mk(np.float32, (33,), 44)
    for b in (nb, jb):
        b.xor_delta_planes_batch([(base, ft)])
        assert b.path_counts()["resident_miss_bytes"] == 0
        b.xor_delta_planes_batch([(lambda: base, ft, "h", "fam")])
    assert set(nb.path_counts()) == set(jb.path_counts())
    assert nb.path_counts()["resident_miss_bytes"] == 0
    assert jb.path_counts()["resident_miss_bytes"] == 33 * 4
