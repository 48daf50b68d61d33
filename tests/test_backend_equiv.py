"""Backend equivalence sweep: the batched jax/Pallas path (interpret mode on
CPU) must be bit-identical to the numpy host path — per-op across dtypes ×
odd/padded shapes × bucket sizes, and end-to-end at the store level (same
corpus, same bytes on disk). This is the workers-1-vs-4 determinism machinery
extended along the backend axis: since containers are pure functions of
(bytes, level, threads, backend-semantics), proving the array transforms
bit-identical proves the containers are too."""

import os

import numpy as np
import pytest

from repro.core.bitx import NumpyBackend, get_backend
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
    stores = {}
    for name, kw in (("numpy", dict(workers=0, backend="numpy")),
                     ("jax", dict(workers=4, backend="jax"))):
        s = ZLLMStore(str(tmp_path / name), **kw)
        for rid, kind in manifest:
            s.ingest_repo(os.path.join(root, rid), rid)
        stores[name] = s
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
    assert nb.path_counts() == {"device_tensors": 0, "device_bytes": 0,
                                "host_tensors": 2, "host_bytes": 777 * 2 + 33 * 8}
    assert jb.path_counts() == {"device_tensors": 1, "device_bytes": 777 * 2,
                                "host_tensors": 1, "host_bytes": 33 * 8}
