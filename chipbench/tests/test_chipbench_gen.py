"""The seeded generator at test size: safetensors layout, determinism, and
the fine-tune kinds the traffic mixes ask for."""

import json
import struct

import numpy as np

import tinycell
from bench.gen import Generator, round_bf16, widen_bf16
from bench.spec import layer_tensors

DENSE_TRAFFIC = {"changed_tensors": ".*", "delta_rel": 0.01, "delta_density": 1.0}
ATTN_TRAFFIC = {"changed_tensors": r"self_attn\.", "delta_rel": 0.01}


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
