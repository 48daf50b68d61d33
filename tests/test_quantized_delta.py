"""The dtype-crossing quantized delta lane (bitxq) + hub-corpus ground truth.

Three layers under test:

* **Codec** — ``bitxq`` dequantize-predict-residual round trips int8 tensors
  bit-exactly against their float base, beats standalone coding when the
  repack sits on the predicted grid, and downgrades to raw/stored when the
  "base" is unrelated noise.
* **Store** — quantized repos (int8 tensors + scale companions, declared
  ``base_model``) ingest through the bitxq lane, survive save/load, gc and
  compact (the stamp — base_dtype/qscale_bits/qzero_point — must be copied
  when compaction rewrites records), and decode bit-identically on the
  numpy and jax backends.
* **Ground truth** — the corpus generator's ``families.json`` labels are
  what ``score_family_clustering`` turns into the CI-gated
  ``zllm.cluster.family_f1`` metric: bit-distance clustering must recover
  the generator's families with and without declared metadata, and a
  quantized member must form its own singleton (dtype crossing defeats
  bit distance BY DESIGN — metadata is the store's path for those repos).
"""

import json
import os

import ml_dtypes
import numpy as np
import pytest

from benchmarks.corpus import (CorpusSpec, make_base_tensors, make_corpus,
                               make_finetune, make_quantized_int4,
                               make_quantized_int8)
from repro.core.bitx import TensorRecord
from repro.core.codecs import CodecRuntime, EncodeInput, get_codec
from repro.core.pipeline import ZLLMStore
from repro.formats import safetensors as st


def _spec(**kw):
    base = dict(n_families=2, finetunes_per_family=1, reuploads_per_family=0,
                lora_per_family=0, vocab_expanded_per_family=0,
                checkpoints_per_family=0, quantized_per_family=1,
                n_layers=1, d_model=48, d_ff=96, vocab=192, seed=13)
    base.update(kw)
    return CorpusSpec(**base)


def _bf16_base(n=4096, seed=5):
    rng = np.random.RandomState(seed)
    return (rng.randn(n) * 0.02).astype(ml_dtypes.bfloat16)


def _int8_repack(base_bf16):
    q = make_quantized_int8({"t": base_bf16})
    return q["t"]


# ---------------------------------------------------------------------------
# Codec layer
# ---------------------------------------------------------------------------

def test_bitxq_pure_repack_all_zero_residual_roundtrip():
    """An int8 repack of its own base lands exactly on the predicted grid:
    the XOR residual is all zero, the frames are far smaller than standalone
    coding of the int8 bytes, and decode recovers them bit-exactly."""
    rt = CodecRuntime()
    base = _bf16_base()
    q = _int8_repack(base)
    out = get_codec("bitxq").encode(
        rt, EncodeInput(data=q, base=base.view(np.uint16).tobytes(),
                        base_dtype="BF16"))
    codec, frames, raw, extras = out
    assert codec == "bitxq" and raw == q.nbytes
    assert extras["base_dtype"] == "BF16" and extras["qzero_point"] == 0
    # the scale bit pattern must decode to a positive finite float32
    scale = np.array(extras["qscale_bits"], np.uint32).view(np.float32)[()]
    assert np.isfinite(scale) and scale > 0
    standalone = len(rt.compress(q.tobytes()))
    assert sum(len(f) for f in frames) < standalone / 5

    rec = TensorRecord("t", "I8", q.shape, "bitxq", "bh", "sh",
                       [len(f) for f in frames], raw, **extras)
    got = get_codec("bitxq").decode(
        rt, rec, frames, np.dtype(np.int8),
        lambda h: base.view(np.uint16).tobytes(), None)
    assert got.dtype == np.int8 and (got == q).all()


def test_bitxq_quantized_finetune_roundtrip():
    """Quantizing a FINE-TUNE but predicting from the family BASE leaves a
    nonzero residual; the lane must still round trip bit-exactly."""
    rt = CodecRuntime()
    spec = _spec()
    rng = np.random.RandomState(spec.seed)
    base = _bf16_base(2048)
    ft = (base.astype(np.float32)
          + (rng.randn(base.size) * 0.005).astype(np.float32)
          ).astype(ml_dtypes.bfloat16)
    q = _int8_repack(ft)  # quantized on the fine-tune's own grid
    out = get_codec("bitxq").encode(
        rt, EncodeInput(data=q, base=base.view(np.uint16).tobytes(),
                        base_dtype="BF16"))
    assert out[0] == "bitxq"
    codec, frames, raw, extras = out
    rec = TensorRecord("t", "I8", q.shape, "bitxq", "bh", "sh",
                       [len(f) for f in frames], raw, **extras)
    got = get_codec("bitxq").decode(
        rt, rec, frames, np.dtype(np.int8),
        lambda h: base.view(np.uint16).tobytes(), None)
    assert (got == q).all()


def test_bitxq_downgrades_on_unrelated_base():
    """Predicting from NOISE leaves a dense residual; the encoder must fall
    back to standalone raw/stored coding (3-tuple, no stamp) rather than
    ship a delta bigger than the data."""
    rt = CodecRuntime()
    rng = np.random.RandomState(9)
    q = rng.randint(-127, 128, 4096).astype(np.int8)
    noise = (rng.randn(4096) * 0.02).astype(ml_dtypes.bfloat16)
    out = get_codec("bitxq").encode(
        rt, EncodeInput(data=q, base=noise.view(np.uint16).tobytes(),
                        base_dtype="BF16"))
    assert out[0] in ("raw", "stored") and len(out) == 3


def test_bitxq_nonfinite_base_elements_are_deterministic():
    """NaN/Inf in the base must quantize to a well-defined prediction (zeroed
    before rint) — int8-casting NaN is platform-dependent, which would break
    the cross-backend container-determinism guarantee."""
    rt = CodecRuntime()
    base = _bf16_base(1024)
    base[::100] = np.float32("nan")
    base[1::100] = np.float32("inf")
    q = _int8_repack(base)
    out = get_codec("bitxq").encode(
        rt, EncodeInput(data=q, base=base.view(np.uint16).tobytes(),
                        base_dtype="BF16"))
    codec, frames, raw, extras = out
    rec = TensorRecord("t", "I8", q.shape, "bitxq", "bh", "sh",
                       [len(f) for f in frames], raw, **extras)
    got = get_codec("bitxq").decode(
        rt, rec, frames, np.dtype(np.int8),
        lambda h: base.view(np.uint16).tobytes(), None)
    assert (got == q).all()


def test_tensor_record_stamp_json_roundtrip():
    """The quant stamp survives index serialization; records WITHOUT a stamp
    serialize exactly as before (old containers stay byte-identical)."""
    r = TensorRecord("t", "I8", (4,), "bitxq", "bh", "sh", [3], 4,
                     base_dtype="BF16", qscale_bits=1065353216, qzero_point=0)
    j = r.to_json()
    back = TensorRecord.from_json(j)
    assert (back.base_dtype, back.qscale_bits, back.qzero_point) == \
        ("BF16", 1065353216, 0)
    plain = TensorRecord("t", "F32", (4,), "zipnn", None, "sh", [3], 16)
    assert not {"base_dtype", "qscale_bits", "qzero_point"} & set(plain.to_json())


# ---------------------------------------------------------------------------
# Store layer
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def qcorpus(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("qhub"))
    manifest = make_corpus(root, _spec())
    families = json.load(open(os.path.join(root, "families.json")))
    return root, manifest, families


def _ingest_all(store, root, manifest):
    store.ingest_repos([(os.path.join(root, rid), rid) for rid, _ in manifest])


def test_store_quantized_repo_takes_bitxq_lane(tmp_path, qcorpus):
    root, manifest, _ = qcorpus
    store = ZLLMStore(str(tmp_path / "s"))
    _ingest_all(store, root, manifest)
    qres = [r for r in store.results if "int8" in r.repo_id]
    assert qres and all(r.n_bitxq > 0 for r in qres)
    assert all(r.base_source == "metadata" for r in qres)
    # the delta lane must make the int8 repack measurably smaller than
    # standalone: the repack of the base itself is near-all-dedup-or-zero
    repack = next(r for r in qres if r.repo_id.startswith("quant0-0"))
    assert repack.reduction > 0.5
    for rid, _ in manifest:
        orig = open(os.path.join(root, rid, "model.safetensors"), "rb").read()
        assert store.retrieve_file(rid, "model.safetensors") == orig


def test_store_bitxq_survives_reload(tmp_path, qcorpus):
    root, manifest, _ = qcorpus
    s1 = ZLLMStore(str(tmp_path / "p"))
    _ingest_all(s1, root, manifest)
    s1.save_index()
    s2 = ZLLMStore(str(tmp_path / "p"))
    assert s2.load_index()
    for rid, _ in manifest:
        orig = open(os.path.join(root, rid, "model.safetensors"), "rb").read()
        assert s2.retrieve_file(rid, "model.safetensors") == orig


def test_store_bitxq_survives_gc_and_compact(tmp_path, qcorpus):
    """Compaction rewrites still-referenced records into fresh containers —
    it must copy the quant stamp (base_dtype/qscale_bits/qzero_point) and
    keep the base tensor reachable, or decode breaks afterwards."""
    root, manifest, _ = qcorpus
    store = ZLLMStore(str(tmp_path / "c"))
    _ingest_all(store, root, manifest)
    # supersede a fine-tune generation so compact has something to do, then
    # delete a quantized repo so gc chews on bitxq bookkeeping too
    ft = next(rid for rid, kind in manifest if kind == "finetune")
    store.ingest_repo(os.path.join(root, ft), ft)
    gone = next(rid for rid, kind in manifest if kind == "quantized_int8"
                and rid.endswith("-1-0"))
    store.delete_repo(gone)
    store.gc()
    store.compact()
    rep = store.fsck(repair=False, spot_check=None)
    assert rep.ok
    for rid, _ in manifest:
        if rid == gone:
            continue
        orig = open(os.path.join(root, rid, "model.safetensors"), "rb").read()
        assert store.retrieve_file(rid, "model.safetensors") == orig


def test_store_bitxq_containers_bit_identical_numpy_vs_jax(tmp_path, qcorpus):
    """The bitxq prediction is pinned to host numpy precisely so the
    container bytes cannot depend on the backend: same corpus, numpy vs
    jax stores, every container file byte-identical."""
    import hashlib
    root, manifest, _ = qcorpus
    digests = {}
    for backend in ("numpy", "jax"):
        s = ZLLMStore(str(tmp_path / backend), backend=backend)
        _ingest_all(s, root, manifest)
        h = hashlib.sha256()
        croot = str(tmp_path / backend)
        for dirpath, _, files in sorted(os.walk(croot)):
            for fn in sorted(files):
                rel = os.path.relpath(os.path.join(dirpath, fn), croot)
                h.update(rel.encode())
                h.update(open(os.path.join(dirpath, fn), "rb").read())
        digests[backend] = h.hexdigest()
        for rid, _ in manifest:
            orig = open(os.path.join(root, rid, "model.safetensors"),
                        "rb").read()
            assert s.retrieve_file(rid, "model.safetensors") == orig
    assert digests["numpy"] == digests["jax"]


# ---------------------------------------------------------------------------
# Generator ground truth + clustering accuracy
# ---------------------------------------------------------------------------

def test_families_json_covers_every_repo(qcorpus):
    root, manifest, families = qcorpus
    assert set(families) == {rid for rid, _ in manifest}
    assert all(v.startswith("family-") for v in families.values())


def test_clustering_recovers_generator_truth(qcorpus):
    """F1 == 1.0 against ground truth over the full-weight same-signature
    kinds — the exact computation behind zllm.cluster.family_f1."""
    from repro.core.clustering import score_family_clustering
    root, manifest, families = qcorpus
    paths, labels = [], []
    for rid, kind in manifest:
        if kind in ("base", "finetune", "reupload", "checkpoint"):
            paths.append(os.path.join(root, rid, "model.safetensors"))
            labels.append(families[rid])
    s = score_family_clustering(paths, labels)
    assert s["f1"] == 1.0 and s["n_clusters"] == 2


def test_clustering_recovers_truth_without_metadata(tmp_path):
    """metadata_prob=0: no fine-tune declares base_model, so family recovery
    rests entirely on sampled bit distance — the paper's §A.0.1 claim.
    sigma_delta sits at the LOW end of the paper's band (E[D] ≈ 3.1 bits at
    σw=0.02, comfortably under the 4-bit threshold): at the band's middle
    the per-file mean rides the threshold and recall is a coin flip, which
    is the paper's 93.5%-not-100% point, not a regression to gate on."""
    from repro.core.clustering import score_family_clustering
    root = str(tmp_path / "nometa")
    manifest = make_corpus(root, _spec(metadata_prob=0.0, sigma_delta=0.001,
                                       finetunes_per_family=2))
    families = json.load(open(os.path.join(root, "families.json")))
    paths, labels = [], []
    for rid, kind in manifest:
        if kind in ("base", "finetune"):
            paths.append(os.path.join(root, rid, "model.safetensors"))
            labels.append(families[rid])
    s = score_family_clustering(paths, labels)
    assert s["f1"] == 1.0


def test_quantized_member_clusters_as_singleton(qcorpus):
    """An int8 repack crosses the dtype/shape signature, so bit distance
    CANNOT place it (singleton component) — documenting why quantized repos
    must declare base_model and why family_f1 scoring excludes them."""
    from repro.core.clustering import cluster_models
    root, manifest, families = qcorpus
    paths = []
    qi = None
    for rid, kind in manifest:
        if kind in ("base", "finetune"):
            paths.append(os.path.join(root, rid, "model.safetensors"))
        elif kind == "quantized_int8" and qi is None:
            qi = len(paths)
            paths.append(os.path.join(root, rid, "model.safetensors"))
    comps = cluster_models(paths)
    assert [qi] in comps


def test_score_family_clustering_validates_lengths():
    from repro.core.clustering import score_family_clustering
    with pytest.raises(ValueError, match="labels"):
        score_family_clustering(["a"], ["x", "y"])


# ---------------------------------------------------------------------------
# Hub-tier generator shapes
# ---------------------------------------------------------------------------

def test_sharded_family_writes_numbered_shards(tmp_path):
    root = str(tmp_path / "sh")
    make_corpus(root, _spec(sharded_families=1, shards=3))
    files = sorted(os.listdir(os.path.join(root, "org0/base-model-0")))
    assert "model-00001-of-00003.safetensors" in files
    assert sum(f.endswith(".safetensors") for f in files) == 3
    # family 1 stays single-file
    assert os.path.exists(os.path.join(root, "org1/base-model-1",
                                       "model.safetensors"))
    # shards partition the tensor set: names disjoint, union == unsharded set
    names = []
    for f in files:
        if f.endswith(".safetensors"):
            names += list(st.load_file(
                os.path.join(root, "org0/base-model-0", f)))
    assert len(names) == len(set(names))


def test_arch_templates_moe_and_ssm(tmp_path):
    """MoE configs get per-expert mats + router, SSM configs a Mamba mixer
    stack with float32 state params — structural signatures from the real
    repro.configs entries at scaled-down widths."""
    rng = np.random.RandomState(0)
    from repro.configs import get_config
    spec = _spec()
    moe = make_base_tensors(spec, rng, get_config("mixtral-8x7b"))
    assert "model.layers.0.block_sparse_moe.gate.weight" in moe
    assert "model.layers.0.block_sparse_moe.experts.0.w1.weight" in moe
    ssm = make_base_tensors(spec, rng, get_config("falcon-mamba-7b"))
    assert "model.layers.0.mixer.in_proj.weight" in ssm
    assert ssm["model.layers.0.mixer.A_log"].dtype == np.float32
    dense = make_base_tensors(spec, rng, None)
    assert "model.layers.0.mlp.gate_proj.weight" in dense


def test_int4_pack_halves_bytes(tmp_path):
    base = {"w": _bf16_base(1000)}
    q4 = make_quantized_int4(base)
    assert q4["w"].dtype == np.uint8 and q4["w"].size == 500
    assert q4["w.quant_scale"].dtype == np.float32


def test_popularity_skew_preserves_budget_and_floor():
    from benchmarks.corpus import _finetune_counts
    flat = _finetune_counts(_spec(n_families=4, finetunes_per_family=3))
    assert flat == [3, 3, 3, 3]
    skewed = _finetune_counts(_spec(n_families=4, finetunes_per_family=3,
                                    popularity_skew=0.8))
    assert sum(skewed) == 12 and min(skewed) >= 1
    assert skewed[0] > skewed[-1]  # family 0 is the popular one


def test_quantized_repos_always_declare_base(tmp_path):
    """Even at metadata_prob=0 the quantized repos carry base_model — the
    dtype crossing leaves metadata as the only family signal."""
    root = str(tmp_path / "qm")
    manifest = make_corpus(root, _spec(metadata_prob=0.0))
    for rid, kind in manifest:
        if kind == "quantized_int8":
            readme = open(os.path.join(root, rid, "README.md")).read()
            assert "base_model:" in readme
