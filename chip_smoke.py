#!/usr/bin/env python3
"""Chip smoke: the store's main path once on a TPU, at published widths.

    python chip_smoke.py [--seed N]       # one chip
    python chip_smoke.py --chips 4        # checkpoint resume onto a 2x2 mesh

One chip. From ``--seed`` it generates a qwen2-7b base checkpoint and a
sparse-delta fine-tune of it at the published widths (d_model 3584, d_ff
18944, vocab 152064, GQA with 4 KV heads, QKV biases; bf16 with float32
norms), cut in depth to one decoder layer plus the embedding and the untied
lm_head: about 2.6 GB per file. It starts the store server in this process
on a ``ZLLMStore(backend="jax")``, PUTs the base and then the fine-tune with
``?base=``, waits for both ingest jobs, GETs both files back and compares
their sha256 with the uploads, compares a few ranged tensor GETs with slices
of the source, and reads ``/stats``: the fine-tune's big tensors must have
gone through the bitx lane and tensors must have gone through the device
kernels.

Four chips (``--chips 4``). It saves two checkpoint steps of one qwen2-7b
decoder layer through ``CheckpointManager`` into a ``ZLLMStore``, restores
the second onto a 2x2 mesh and onto one device, and checks that every shard
holds its slice of the one-device restore byte for byte and that the arrays
span the 4 devices.

Earlier lines carry bring-up facts (sizes, device/host tensor counts,
compiles, wall times of this run; none is a benchmark metric). The last line
is ``{"ok": true, "device": {...}}``. Without a TPU, or on any failed check,
the script exits non-zero and prints no such line. Everything runs in this
one process: the chip belongs to one process at a time.
"""

from __future__ import annotations

import argparse
import hashlib
import http.client
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

_CHUNK = 1 << 24          # elements per generation chunk (64 MiB of float32)
_DELTA_EVERY = 16         # the fine-tune changes about 1 element in 16
_BIG_TENSOR = 1 << 20     # "big" tensors: at least 1 MiB


def log(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


def qwen2_tensors(cfg, n_layers: int = 1, embeddings: bool = True):
    """(name, shape, safetensors tag) of a qwen2 checkpoint cut to
    ``n_layers`` decoder layers, in HF naming and order."""
    d, f, v = cfg.d_model, cfg.d_ff, cfg.vocab
    kv = cfg.n_kv_heads * (cfg.head_dim or d // cfg.n_heads)
    out = [("model.embed_tokens.weight", (v, d), "BF16")] if embeddings else []
    for i in range(n_layers):
        p = f"model.layers.{i}."
        out += [(p + "self_attn.q_proj.weight", (d, d), "BF16"),
                (p + "self_attn.q_proj.bias", (d,), "BF16"),
                (p + "self_attn.k_proj.weight", (kv, d), "BF16"),
                (p + "self_attn.k_proj.bias", (kv,), "BF16"),
                (p + "self_attn.v_proj.weight", (kv, d), "BF16"),
                (p + "self_attn.v_proj.bias", (kv,), "BF16"),
                (p + "self_attn.o_proj.weight", (d, d), "BF16"),
                (p + "mlp.gate_proj.weight", (f, d), "BF16"),
                (p + "mlp.up_proj.weight", (f, d), "BF16"),
                (p + "mlp.down_proj.weight", (d, f), "BF16"),
                (p + "input_layernorm.weight", (d,), "F32"),
                (p + "post_attention_layernorm.weight", (d,), "F32")]
    if embeddings:
        out += [("model.norm.weight", (d,), "F32"),
                ("lm_head.weight", (v, d), "BF16")]
    return out


def random_tensor(rng, shape, tag):
    """N(0, 0.02) weights generated in float32 chunks; bf16 as its uint16
    bit view (the float32 word truncated to its top half)."""
    import numpy as np
    n = int(np.prod(shape))
    out = np.empty(n, np.float32 if tag == "F32" else np.uint16)
    for s in range(0, n, _CHUNK):
        x = rng.standard_normal(min(_CHUNK, n - s), dtype=np.float32)
        x *= 0.02
        out[s:s + x.size] = x if tag == "F32" else x.view(np.uint32) >> 16
    return out.reshape(shape)


def sparse_delta(rng, arr):
    """A fine-tune of ``arr``: about 1 element in ``_DELTA_EVERY`` has its
    low mantissa bits changed (every tensor differs from its base)."""
    import numpy as np
    words = arr.reshape(-1).view(np.uint16 if arr.itemsize == 2 else np.uint32).copy()
    idx = rng.integers(0, words.size, max(1, words.size // _DELTA_EVERY))
    words[idx] ^= rng.integers(1, 8, idx.size).astype(words.dtype)
    return words.view(arr.dtype).reshape(arr.shape)


def write_pair(cfg, seed: int, base_path: str, ft_path: str) -> dict:
    """Generate the base and fine-tune files; returns {name: nbytes}."""
    import numpy as np
    from repro.formats import safetensors as st
    rng = np.random.default_rng(seed)
    spec = qwen2_tensors(cfg)
    base = {name: random_tensor(rng, shape, tag) for name, shape, tag in spec}
    tags = {name: tag for name, _, tag in spec}
    st.save_file(base, base_path, metadata={"model": cfg.name}, dtype_tags=tags)
    ft = {name: sparse_delta(rng, arr) for name, arr in base.items()}
    del base
    st.save_file(ft, ft_path, metadata={"model": cfg.name + "-ft"}, dtype_tags=tags)
    return {name: int(arr.nbytes) for name, arr in ft.items()}


# -- HTTP client ----------------------------------------------------------------

class Client:
    def __init__(self, host: str, port: int):
        self.host, self.port = host, port

    def _conn(self):
        return http.client.HTTPConnection(self.host, self.port, timeout=1800,
                                          blocksize=1 << 20)

    def json(self, method: str, path: str):
        c = self._conn()
        try:
            c.request(method, path)
            r = c.getresponse()
            body = r.read()
        finally:
            c.close()
        if r.status not in (200, 202):
            raise RuntimeError(f"{method} {path}: HTTP {r.status} {body[:300]!r}")
        return json.loads(body)

    def put_file(self, path: str, src: str):
        c = self._conn()
        try:
            with open(src, "rb") as f:
                c.request("PUT", path, body=f,
                          headers={"Content-Length": str(os.path.getsize(src))})
            r = c.getresponse()
            body = r.read()
        finally:
            c.close()
        if r.status != 202:
            raise RuntimeError(f"PUT {path}: HTTP {r.status} {body[:300]!r}")
        return json.loads(body)

    def get_sha256(self, path: str):
        c = self._conn()
        h, n = hashlib.sha256(), 0
        try:
            c.request("GET", path)
            r = c.getresponse()
            if r.status != 200:
                raise RuntimeError(f"GET {path}: HTTP {r.status} {r.read()[:300]!r}")
            while True:
                chunk = r.read(8 << 20)
                if not chunk:
                    break
                h.update(chunk)
                n += len(chunk)
        finally:
            c.close()
        return h.hexdigest(), n

    def get_range(self, path: str, start: int, end: int) -> bytes:
        c = self._conn()
        try:
            c.request("GET", path, headers={"Range": f"bytes={start}-{end}"})
            r = c.getresponse()
            body = r.read()
        finally:
            c.close()
        if r.status != 206:
            raise RuntimeError(f"GET {path} range: HTTP {r.status} {body[:300]!r}")
        return body

    def wait_job(self, job_id: str, timeout: float = 1200.0) -> dict:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            job = self.json("GET", f"/admin/jobs?job={job_id}")
            if job["state"] == "done":
                return job
            if job["state"] == "failed":
                raise RuntimeError(f"ingest job {job_id} failed: {job.get('error')}")
            time.sleep(0.2)
        raise RuntimeError(f"ingest job {job_id} not done after {timeout:.0f}s")


def sha256_file(path: str) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as f:
        for chunk in iter(lambda: f.read(8 << 20), b""):
            h.update(chunk)
    return h.hexdigest()


# -- phases -----------------------------------------------------------------------

def one_chip(cfg, seed: int, workdir: str) -> None:
    """PUT + GET of a base and fine-tune through the store server."""
    from repro.core.pipeline import ZLLMStore
    from repro.formats.safetensors import SafetensorsFile
    from repro.serve.store_server import ServerThread

    t0 = time.perf_counter()
    src = os.path.join(workdir, "src")
    os.makedirs(src)
    files = {"base": os.path.join(src, "base.safetensors"),
             "ft": os.path.join(src, "ft.safetensors")}
    ft_sizes = write_pair(cfg, seed, files["base"], files["ft"])
    digests = {k: sha256_file(p) for k, p in files.items()}
    log(f"generated {cfg.name} base + fine-tune, "
        f"{os.path.getsize(files['base'])} + {os.path.getsize(files['ft'])} bytes "
        f"(bring-up wall {time.perf_counter() - t0:.1f}s)")

    repos = {"base": "qwen2-7b-base", "ft": "qwen2-7b-ft"}
    store = ZLLMStore(os.path.join(workdir, "store"), backend="jax", workers=8)
    try:
        if store.summary()["array_backend"] != "jax":
            raise RuntimeError(f"store runs {store.summary()['array_backend']}, not jax")
        with ServerThread(store) as srv:
            cli = Client(srv.host, srv.port)
            t1 = time.perf_counter()
            for kind in ("base", "ft"):
                q = f"?base={repos['base']}" if kind == "ft" else ""
                put = cli.put_file(f"/repo/{repos[kind]}/file/model.safetensors{q}",
                                   files[kind])
                job = cli.wait_job(put["job_id"])
                row = job["results"][0]
                log(f"PUT {repos[kind]}: job {put['job_id']} done, "
                    f"n_tensors {row['n_tensors']} n_bitx {row['n_bitx']} "
                    f"stored {row['stored_bytes']} of {row['raw_bytes']} bytes")
            log(f"ingest of both files: bring-up wall {time.perf_counter() - t1:.1f}s")

            t2 = time.perf_counter()
            for kind in ("base", "ft"):
                got, n = cli.get_sha256(f"/repo/{repos[kind]}/file/model.safetensors")
                if got != digests[kind]:
                    raise RuntimeError(f"GET {repos[kind]}: sha256 {got} != upload "
                                       f"{digests[kind]} ({n} bytes)")
                log(f"GET {repos[kind]}: {n} bytes, sha256 matches the upload")
            log(f"GET of both files: bring-up wall {time.perf_counter() - t2:.1f}s")

            with SafetensorsFile(files["ft"]) as sf:
                infos = {ti.name: ti for ti in sf.infos}
                for name in ("lm_head.weight", "model.layers.0.mlp.down_proj.weight",
                             "model.layers.0.input_layernorm.weight",
                             "model.layers.0.self_attn.k_proj.bias"):
                    size = infos[name].nbytes
                    start, end = size // 3, min(size - 1, size // 3 + 65535)
                    body = cli.get_range(f"/repo/{repos['ft']}/tensor/{name}", start, end)
                    if body != bytes(sf.tensor_bytes(name)[start:end + 1]):
                        raise RuntimeError(f"ranged GET {name} [{start}, {end}] differs")
                    log(f"ranged GET {name} bytes {start}-{end}: matches the source")

            stats = cli.json("GET", "/stats")["store"]
    finally:
        store.close()

    big = sum(n for n in ft_sizes.values() if n >= _BIG_TENSOR)
    codec_bytes = stats["codec_bytes"]
    log(f"store: raw {stats['raw_bytes']} bytes, stored {stats['stored_bytes']} "
        f"bytes, codec_bytes {json.dumps(codec_bytes, sort_keys=True)}")
    if codec_bytes.get("bitx", 0) < big:
        raise RuntimeError(f"bitx lane took {codec_bytes.get('bitx', 0)} bytes, "
                           f"fewer than the fine-tune's {big} bytes of big tensors")
    path = stats["array_path"]
    log(f"device kernels: {path['device_tensors']} tensors, {path['device_bytes']} "
        f"bytes; host path: {path['host_tensors']} tensors, {path['host_bytes']} bytes")
    if path["device_tensors"] == 0:
        raise RuntimeError("no tensor went through the device kernels")


def four_chips(cfg, seed: int, workdir: str, devices) -> None:
    """Save a checkpoint stream through the store, resume it onto a mesh."""
    import ml_dtypes
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding
    from repro.checkpoint.manager import CheckpointManager
    from repro.core.pipeline import ZLLMStore

    rng = np.random.default_rng(seed)
    spec = qwen2_tensors(cfg, embeddings=False)

    def as_param(arr, tag):
        return arr if tag == "F32" else arr.view(ml_dtypes.bfloat16)

    step1 = {n: random_tensor(rng, s, t) for n, s, t in spec}
    step2 = {n: sparse_delta(rng, a) for n, a in step1.items()}
    step1 = {n: as_param(step1[n], t) for n, _, t in spec}
    step2 = {n: as_param(step2[n], t) for n, _, t in spec}
    log(f"one {cfg.name} decoder layer at published widths, "
        f"{sum(a.nbytes for a in step2.values())} bytes per step")

    mesh = Mesh(np.array(devices[:4]).reshape(2, 2), ("data", "model"))
    shard = {n: NamedSharding(mesh, PartitionSpec("data", "model") if a.ndim == 2
                              else PartitionSpec("model"))
             for n, a in step2.items()}
    one = {n: SingleDeviceSharding(devices[0]) for n in step2}
    store = ZLLMStore(os.path.join(workdir, "store"), backend="jax", workers=8)
    try:
        mgr = CheckpointManager(os.path.join(workdir, "run"), store=store,
                                run_id="qwen2-7b-run", keep_plain=False,
                                save_optimizer=False)
        t0 = time.perf_counter()
        mgr.save(1, step1)
        mgr.save(2, step2)
        log(f"saved steps 1 and 2 through the store: stored "
            f"{store.summary()['stored_bytes']} of {store.summary()['raw_bytes']} "
            f"bytes (bring-up wall {time.perf_counter() - t0:.1f}s)")
        _, on_mesh, _ = mgr.restore_sharded(mesh, shard, step=2)
        _, on_one, _ = mgr.restore_sharded(None, one, step=2)
        path = store.summary()["array_path"]
    finally:
        store.close()
    if path["device_tensors"] == 0:
        raise RuntimeError("no tensor went through the device kernels")

    n_shards = 0
    for name, want in step2.items():
        ref = np.asarray(on_one[name])
        if ref.tobytes() != want.tobytes():
            raise RuntimeError(f"{name}: one-device restore differs from the save")
        arr = on_mesh[name]
        devs = {s.device for s in arr.addressable_shards}
        if len(devs) != 4 or arr.sharding.mesh.devices.size != 4:
            raise RuntimeError(f"{name}: spread over {len(devs)} devices, not 4")
        for s in arr.addressable_shards:
            if np.asarray(s.data).tobytes() != ref[s.index].tobytes():
                raise RuntimeError(f"{name}: shard on {s.device} differs from "
                                   f"its slice {s.index} of the one-device restore")
            n_shards += 1
    log(f"restored {len(step2)} tensors onto a 2x2 mesh: {n_shards} shards on "
        f"{len(devices)} devices, each byte-identical to its slice of the "
        f"one-device restore")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.compile_cache import configure_compile_cache
    cache_dir = configure_compile_cache()
    import jax
    from repro.configs.qwen2_7b import CONFIG

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devices[0].platform}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} devices",
              file=sys.stderr)
        return 2
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    log(f"device {json.dumps(device)}, compile cache {cache_dir}")

    compiles = {"n": 0, "secs": 0.0}

    def on_duration(event, duration, *_, **__):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles["n"] += 1
            compiles["secs"] += duration
    jax.monitoring.register_event_duration_secs_listener(on_duration)

    workdir = os.path.join(HERE, ".smoke")
    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir)
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            four_chips(CONFIG, args.seed, workdir, devices)
        else:
            log(f"{CONFIG.name} at published widths, cut in depth to 1 of "
                f"{CONFIG.n_layers} decoder layers plus the embedding and "
                f"the untied lm_head")
            one_chip(CONFIG, args.seed, workdir)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    stats = devices[0].memory_stats() or {}
    log(f"{compiles['n']} backend compiles ({compiles['secs']:.1f}s), peak device "
        f"memory {stats.get('peak_bytes_in_use', 'not reported')} bytes, "
        f"bring-up wall {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
