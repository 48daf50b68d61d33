"""The seeded generator at test size: safetensors layout, determinism, and
the fine-tune kinds the traffic mixes ask for."""

import hashlib
import json
import struct

import ml_dtypes
import numpy as np
import pytest

import tinycell
from bench import gen, registry
from bench.gen import Generator, round_bf16, widen_bf16
from bench.spec import layer_tensors

DENSE_TRAFFIC = {"changed_tensors": ".*", "delta_rel": 0.01, "delta_density": 1.0}
ATTN_TRAFFIC = {"changed_tensors": r"self_attn\.", "delta_rel": 0.01}
HALF_TRAFFIC = {"changed_tensors": r"mlp\.", "delta_rel": 0.01, "delta_density": 0.5}


def _parse(upload):
    blob = b"".join(bytes(s) for s in upload.segments)
    (n,) = struct.unpack("<Q", blob[:8])
    header = json.loads(blob[8:8 + n])
    return header, blob[8 + n:]


def test_upload_parses_as_safetensors_in_hf_order():
    g = Generator(tinycell.DENSE, DENSE_TRAFFIC, seed=5, threads=2)
    try:
        up = g.upload(1)
        header, data = _parse(up)
    finally:
        g.close()
    names = [k for k in header if k != "__metadata__"]
    assert names == [t.name for t in layer_tensors(tinycell.DENSE)]
    assert names[:3] == ["model.layers.0.self_attn.q_proj.weight",
                         "model.layers.0.self_attn.q_proj.bias",
                         "model.layers.0.self_attn.k_proj.weight"]
    end = 0
    for t in layer_tensors(tinycell.DENSE):
        h = header[t.name]
        assert h["dtype"] == t.dtype and h["shape"] == list(t.shape)
        assert h["data_offsets"] == [end, end + t.nbytes]
        end += t.nbytes
    assert len(data) == end == up.nbytes - up.segments[0].nbytes
    assert (len(up.segments[0]) - 8) % 8 == 0


def test_published_layer_sizes():
    from bench import registry
    bm = registry.load_benchmark()
    qwen = layer_tensors(registry.config(bm, "qwen2-7b-hub"))
    mix = layer_tensors(registry.config(bm, "mixtral-8x7b-hub"))
    assert (len(qwen), sum(t.nbytes for t in qwen)) == (12, 466129920)
    assert (len(mix), sum(t.nbytes for t in mix)) == (31, 2902540288)
    assert [t.shape for t in mix if t.name.endswith("gate.weight")] == [(8, 4096)]
    assert max(t.nbytes for t in qwen) == 135790592


def test_seeds_differ_and_repeat_across_thread_counts():
    a = Generator(tinycell.DENSE, DENSE_TRAFFIC, seed=2**33 + 1, threads=1)
    b = Generator(tinycell.DENSE, DENSE_TRAFFIC, seed=2**33 + 1, threads=3)
    c = Generator(tinycell.DENSE, DENSE_TRAFFIC, seed=2**33 + 2, threads=2)
    try:
        assert a.upload(3).digests == b.upload(3).digests
        assert a.upload(0).digests[1:] != c.upload(0).digests[1:]
        assert a.upload(3).digests[1:] != a.upload(4).digests[1:]
    finally:
        for g in (a, b, c):
            g.close()


def test_dense_delta_moves_low_bits_of_every_tensor():
    g = Generator(tinycell.DENSE, DENSE_TRAFFIC, seed=11, threads=2)
    try:
        base, ft = g.upload(0), g.upload(1)
        assert all(x != y for x, y in zip(base.digests[1:], ft.digests[1:]))
        name = "model.layers.0.mlp.up_proj.weight"
        b = np.frombuffer(base.tensor(name), np.uint16)
        f = np.frombuffer(ft.tensor(name), np.uint16)
        x = b ^ f
        assert (x >> 8 == 0).mean() > 0.99      # sign, exponent, high mantissa stay
        assert (x != 0).mean() > 0.5            # most elements move
        rel = np.abs(widen_bf16(f) / widen_bf16(b) - 1)
        assert np.median(rel) < 0.02
    finally:
        g.close()


def test_attention_only_finetune_keeps_experts_byte_identical():
    g = Generator(tinycell.MOE, ATTN_TRAFFIC, seed=13, threads=2)
    try:
        base, ft = g.upload(0), g.upload(1)
    finally:
        g.close()
    for name, b, f in zip(ft.names[1:], base.digests[1:], ft.digests[1:]):
        if "self_attn." in name:
            assert b != f, name
        else:
            assert b == f, name
            assert bytes(base.tensor(name)) == bytes(ft.tensor(name))
    assert any("experts.1.w2" in n for n in ft.names)


def test_round_bf16_is_nearest_even():
    x = np.array([1.0, 1.0 + 2**-8, 1.0 + 3 * 2**-8, -2.5, 1e-3], np.float32)
    got = widen_bf16(round_bf16(x))
    want = np.array([1.0, 1.0, 1.0 + 2**-6, -2.5, 0.00099945068359375], np.float32)
    assert np.array_equal(got, want)


def test_changed_bytes_are_the_tensors_a_finetune_changes():
    dense = Generator(tinycell.DENSE, DENSE_TRAFFIC, seed=17, threads=1)
    attn = Generator(tinycell.MOE, ATTN_TRAFFIC, seed=17, threads=1)
    try:
        assert dense.changed_bytes == dense.upload(1).nbytes - len(dense.header)
        base, ft = attn.upload(0), attn.upload(1)
        differ = sum(len(ft.tensor(n)) for n, b, f in
                     zip(ft.names[1:], base.digests[1:], ft.digests[1:]) if b != f)
        assert attn.changed_bytes == differ > 0
    finally:
        dense.close()
        attn.close()


# sha256 of the full-width headers, and of the concatenated segment digests of
# a base (stream 0) and a fine-tune (stream 1) at seed 2**33 + 16, as the
# generator made them before layouts moved to files of their own
PINNED_HEADERS = {
    "qwen2-7b-hub": "98e492725704bc615631f9e36aea07160b39177b164d048ad69b65cb82b057ba",
    "mixtral-8x7b-hub": "ece2f7becb8f0d38a6fdf1c1edd5e844996de75b404350ed2f4435006b9703c0",
}
PINNED_UPLOADS = [
    ("dense", 0, "5becd187a6e99fc08166278bd0a69dad6d3a349e64196e6a17f2c6cb27c3e0c6"),
    ("dense", 1, "f80efeb5cff6a73a206cce4f93ef66589a6e9fc49e16860015f2255674c1aaac"),
    ("moe", 0, "680a85753a8448d66601b53ca691f350e9a75183486cdc2fce3f2d290d1fd0e0"),
    ("moe", 1, "d22d8da50c8256d67f8b23d6dc4143204ec187eb585b73d721ef906cad211529"),
    ("dense-half", 1, "cfe327e59f48e7657cbec3b7a6e59d9dbcb5f82d7a9c6b07bae2644449149e0b"),
]
PINNED_CELLS = {"dense": (tinycell.DENSE, DENSE_TRAFFIC),
                "moe": (tinycell.MOE, ATTN_TRAFFIC),
                "dense-half": (tinycell.DENSE, HALF_TRAFFIC)}


@pytest.mark.parametrize("config", sorted(PINNED_HEADERS))
def test_published_headers_are_pinned(config):
    tensors = layer_tensors(registry.config(registry.load_benchmark(), config))
    got = hashlib.sha256(gen.header_bytes(tensors)).hexdigest()
    assert got == PINNED_HEADERS[config]


@pytest.mark.parametrize("cell,stream,digest", PINNED_UPLOADS)
def test_upload_digests_are_pinned(cell, stream, digest):
    g = Generator(*PINNED_CELLS[cell], seed=2**33 + 16, threads=2)
    try:
        digests = g.upload(stream).digests
    finally:
        g.close()
    assert hashlib.sha256("".join(digests).encode()).hexdigest() == digest


def test_f16_tensors_take_two_bytes_as_their_header_says():
    cfg = dict(tinycell.DENSE, norm_dtype="F16")
    g = Generator(cfg, DENSE_TRAFFIC, seed=19, threads=2)
    try:
        header, _ = _parse(g.upload(1))
        name = "model.layers.0.input_layernorm.weight"
        seg = g.upload(0).tensor(name)
    finally:
        g.close()
    a, b = header[name]["data_offsets"]
    assert header[name]["dtype"] == "F16" and b - a == seg.nbytes == 2 * 256
    i = [t.name for t in layer_tensors(cfg)].index(name)
    want = gen._rng(19, 0, i, 0).standard_normal(256, dtype=np.float32) * np.float32(0.02)
    assert np.array_equal(np.frombuffer(seg, np.float16), want.astype(np.float16))


# -- FP8 block-scaled weights, from a layout, configuration and traffic mix
#    that live in files of their own --------------------------------------

FP8_LAYOUT = '''"""A DeepSeek-V3-like layer at test widths: latent attention, a router
and routed experts, FP8 weights with block scales."""

from bench.spec import Tensor, linear, norms


def layer_tensors(cfg):
    d, f, n = cfg["hidden_size"], cfg["moe_intermediate_size"], cfg["n_routed_experts"]
    out = []
    for i in range(cfg["num_hidden_layers"]):
        a, m = f"model.layers.{i}.self_attn.", f"model.layers.{i}.mlp."
        out += linear(cfg, a + "q_a_proj.weight", (cfg["q_lora_rank"], d))
        out.append(Tensor(a + "q_a_layernorm.weight", (cfg["q_lora_rank"],), "BF16"))
        out += linear(cfg, a + "kv_a_proj_with_mqa.weight",
                      (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"], d))
        out += linear(cfg, a + "o_proj.weight", (d, d))
        out.append(Tensor(m + "gate.weight", (n, d), "BF16"))
        out.append(Tensor(m + "gate.e_score_correction_bias", (n,), "F32"))
        for e in range(n):
            for proj, shape in (("gate_proj", (f, d)), ("up_proj", (f, d)),
                                ("down_proj", (d, f))):
                out += linear(cfg, m + f"experts.{e}.{proj}.weight", shape)
        out += norms(cfg, f"model.layers.{i}.")
    return out
'''
FP8_CONFIG = {"model_type": "tinyfp8", "hidden_size": 320, "q_lora_rank": 192,
              "kv_lora_rank": 64, "qk_rope_head_dim": 8, "n_routed_experts": 2,
              "moe_intermediate_size": 96, "num_hidden_layers": 1,
              "initializer_range": 0.02,
              "quantization_config": {"quant_method": "fp8", "fmt": "e4m3",
                                      "weight_block_size": [128, 128]}}
FP8_TRAFFIC = {"clients": 2, "uploads_per_client": 2,
               "changed_tensors": r"self_attn\.|experts\.", "delta_rel": 0.01}
E4M3 = ml_dtypes.float8_e4m3fn


@pytest.fixture
def fp8_cell(tmp_path, monkeypatch):
    """The configuration and traffic mix of a new architecture, found by the
    registry from new files alone; one stripe of 128 rows per chunk."""
    for d in ("layouts", "configs", "traffic"):
        (tmp_path / d).mkdir()
    (tmp_path / "layouts" / "tinyfp8.py").write_text(FP8_LAYOUT)
    (tmp_path / "configs" / "tinyfp8.json").write_text(json.dumps(FP8_CONFIG))
    (tmp_path / "traffic" / "fp8-ft.json").write_text(json.dumps(FP8_TRAFFIC))
    monkeypatch.setattr(registry, "BENCH_DIR", str(tmp_path))
    monkeypatch.setattr(registry, "REPO", str(tmp_path))
    monkeypatch.setattr(gen, "CHUNK", 128 * 320)
    bm = {"configs": [{"name": "tinyfp8", "file": "configs/tinyfp8.json"}]}
    return registry.config(bm, "tinyfp8"), registry.traffic("fp8-ft")


def _fp8_weights(up):
    """name -> (codes as float32, scales) of every block-scaled weight."""
    out = {}
    for name in up.names:
        if name.endswith("_scale_inv"):
            w = name[:-len("_scale_inv")]
            q = np.frombuffer(up.tensor(w), np.uint8).view(E4M3).astype(np.float32)
            out[w] = (q, np.frombuffer(up.tensor(name), np.float32))
    return out


def _ref_quantize(w):
    """A plain per-block quantizer: s = max|w| / 448, q = e4m3(w / s)."""
    rows, cols = w.shape
    q = np.zeros(w.shape, np.uint8)
    s = np.zeros((-(-rows // 128), -(-cols // 128)), np.float32)
    for r in range(0, rows, 128):
        for c in range(0, cols, 128):
            blk = w[r:r + 128, c:c + 128]
            s[r // 128, c // 128] = np.abs(blk).max() / np.float32(448)
            q[r:r + 128, c:c + 128] = (blk / s[r // 128, c // 128]).astype(E4M3).view(np.uint8)
    return q, s


def _ref_dequantize(q, s):
    """Codes (float32) times their block's scale."""
    w = q.copy()
    for r in range(s.shape[0]):
        for c in range(s.shape[1]):
            w[r * 128:(r + 1) * 128, c * 128:(c + 1) * 128] *= s[r, c]
    return w


def _ref_stripes(rows, cols):
    """The generator's chunks of a block-scaled weight: whole stripes of 128
    rows, about ``gen.CHUNK`` elements each (one stripe in these tests)."""
    step = 128 * max(1, gen.CHUNK // (128 * cols))
    return [(c, r, min(rows, r + step)) for c, r in enumerate(range(0, rows, step))]


def test_fp8_upload_parses_with_block_scales(fp8_cell):
    cfg, traffic = fp8_cell
    g = Generator(cfg, traffic, seed=2**33 + 21, threads=2)
    try:
        up = g.upload(1)
    finally:
        g.close()
    header, data = _parse(up)
    names = [k for k in header if k != "__metadata__"]
    assert names == up.names[1:]
    assert names[:2] == ["model.layers.0.self_attn.q_a_proj.weight",
                         "model.layers.0.self_attn.q_a_proj.weight_scale_inv"]
    end, fp8 = 0, 0
    for name in names:
        h = header[name]
        a, b = h["data_offsets"]
        assert a == end and b - a == up.tensor(name).nbytes
        end = b
        if h["dtype"] == "F8_E4M3":
            fp8 += 1
            rows, cols = h["shape"]
            assert b - a == rows * cols
            sc = header[name + "_scale_inv"]
            assert sc["dtype"] == "F32"
            assert sc["shape"] == [-(-rows // 128), -(-cols // 128)]
    assert fp8 == 3 + 2 * 3 and len(data) == end
    assert header["model.layers.0.self_attn.kv_a_proj_with_mqa.weight_scale_inv"][
        "shape"] == [1, 3]
    assert header["model.layers.0.mlp.gate.weight"]["dtype"] == "BF16"


@pytest.mark.parametrize("threads,density", [(1, 1.0), (3, 0.5)])
def test_fp8_codes_and_scales_equal_a_plain_quantizer(fp8_cell, threads, density):
    cfg, traffic = fp8_cell
    seed = 2**33 + 23
    g = Generator(cfg, dict(traffic, delta_density=density), seed=seed,
                  threads=threads)
    try:
        base, ft = g.upload(0), g.upload(2)
    finally:
        g.close()
    tensors = layer_tensors(cfg)
    index = {t.name: i for i, t in enumerate(tensors)}
    for name in _fp8_weights(base):
        i, (rows, cols) = index[name], tensors[index[name]].shape
        stripes = _ref_stripes(rows, cols)
        w = np.concatenate([gen._rng(seed, 0, i, c).standard_normal(
            (r1 - r0, cols), dtype=np.float32) * np.float32(0.02)
            for c, r0, r1 in stripes])
        q, s = _ref_quantize(w)
        w = _ref_dequantize(q.view(E4M3).astype(np.float32), s)
        for c, r0, r1 in stripes:
            rng = gen._rng(seed, 2, i, c)
            new = rng.standard_normal((r1 - r0, cols), dtype=np.float32)
            new = (new * np.float32(0.01) + np.float32(1)) * w[r0:r1]
            keep = rng.random(new.shape, dtype=np.float32) >= density
            new[keep] = w[r0:r1][keep]
            w[r0:r1] = new
        q2, s2 = _ref_quantize(w)
        for up, (qq, ss) in ((base, (q, s)), (ft, (q2, s2))):
            assert np.array_equal(np.frombuffer(up.tensor(name), np.uint8),
                                  qq.ravel()), name
            assert np.array_equal(np.frombuffer(up.tensor(name + "_scale_inv"),
                                                np.float32), ss.ravel()), name


@pytest.mark.parametrize("delta_rel", [0.01, 0.05])
def test_fp8_finetune_moves_codes_and_scales_by_delta_rel(fp8_cell, delta_rel):
    cfg, traffic = fp8_cell
    g = Generator(cfg, dict(traffic, delta_rel=delta_rel), seed=2**33 + 25, threads=2)
    try:
        base, ft = g.upload(0), g.upload(1)
        changed = g.changed_bytes
    finally:
        g.close()
    shapes = {t.name: t.shape for t in layer_tensors(cfg)}
    rel, blocks, moved = [], 0, 0
    fine = _fp8_weights(ft)
    for name, (q, s) in _fp8_weights(base).items():
        (q2, s2), (rows, cols) = fine[name], shapes[name]
        nb = (-(-rows // 128), -(-cols // 128))
        q, q2 = q.reshape(rows, cols), q2.reshape(rows, cols)
        w, w2 = _ref_dequantize(q, s.reshape(nb)), _ref_dequantize(q2, s2.reshape(nb))
        normal = (np.abs(q) >= 2**-6) & (np.abs(q2) >= 2**-6)
        rel.append(w2[normal] / w[normal] - 1)
        for r in range(0, rows, 128):
            for c in range(0, cols, 128):
                blocks += 1
                moved += bool((q[r:r + 128, c:c + 128] != q2[r:r + 128, c:c + 128]).any())
    rel = np.concatenate(rel)
    assert moved > 0.75 * blocks
    # each kept value is (1 + eps) * (1 + r): eps ~ N(0, delta_rel), |r| <= 2**-4
    assert np.abs(rel).max() <= (1 + 6 * delta_rel) * (1 + 2**-4) - 1
    assert 0.5 * delta_rel < np.abs(rel).mean() < 2 * delta_rel
    # both tensors of every changed pair, nothing else
    for name, b, f in zip(ft.names[1:], base.digests[1:], ft.digests[1:]):
        assert (b != f) == ("self_attn." in name or "experts." in name), name
    assert changed == sum(len(ft.tensor(n)) for n, b, f in
                          zip(ft.names[1:], base.digests[1:], ft.digests[1:]) if b != f)


def test_fp8_scale_follows_its_weight(fp8_cell):
    cfg, traffic = fp8_cell
    pat = r"kv_a_proj_with_mqa\.weight$"
    g = Generator(cfg, dict(traffic, changed_tensors=pat), seed=2**33 + 27, threads=2)
    try:
        base, ft = g.upload(0), g.upload(1)
    finally:
        g.close()
    differ = [n for n, b, f in zip(ft.names[1:], base.digests[1:], ft.digests[1:])
              if b != f]
    assert differ == ["model.layers.0.self_attn.kv_a_proj_with_mqa.weight",
                      "model.layers.0.self_attn.kv_a_proj_with_mqa.weight_scale_inv"]
