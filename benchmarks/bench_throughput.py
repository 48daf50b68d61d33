"""Paper Table 4: data ingestion and retrieval throughput.

Methods: HF-style ChunkDedup (FastCDC), ZipNN (+FileDedup), zstd-only, and
zLLM (TensorDedup + BitX + zstd). Single-core CPU numbers — the paper's
absolute MB/s (48-core EPYC + AVX C++) are not reproducible here; the
RELATIVE ordering (CDC ≪ zstd < ZipNN < zLLM ingest; retrieval all ≫ CDC) is
the claim under test. The per-method bytes/s include all hashing + family
matching + entropy coding, as in the paper.

The ``--workers`` sweep exercises the pipelined parallel engine (paper
§4.4.5): the same corpus is ingested serially and with a worker pool, and
the per-setting ingest/retrieve MB/s are recorded so throughput regressions
show up in CI (``--tiny`` runs a seconds-scale smoke corpus).

    PYTHONPATH=src python -m benchmarks.bench_throughput [--scale S] [--workers 1,4] [--tiny]
"""

from __future__ import annotations

import asyncio
import os
import shutil
import time

import numpy as np
import zstandard as zstd

from benchmarks.common import Ctx, Timer, chain_copy, corpus_bytes, emit
from repro.core.bitx import ENTROPY_BACKEND
from repro.core.chunkdedup import ChunkDedup, FastCDC
from repro.core.pipeline import ZLLMStore


# built by workers_sweep (which saves its index there) and then fronted by
# serving_bench from a fresh load — one constant so the coupling is visible
PIPELINED_STORE_ROOT = "/tmp/repro-bench-zllm-pipelined"


def _mbps(nbytes: int, secs: float) -> float:
    return round(nbytes / 2**20 / secs, 1) if secs > 0 else float("inf")


def _retrieve_all(ctx: Ctx, store) -> None:
    """Retrieve every weight file of every repo (sharded repos have several)."""
    for rid, _ in ctx.manifest:
        for path in ctx.repo_files(rid):
            store.retrieve_file(rid, os.path.basename(path), verify=False)


def family_scoring(ctx: Ctx, store) -> dict:
    """The CI-gated accuracy/efficiency figures the synthetic hub's ground
    truth makes scorable (flattened to ``zllm.cluster.family_f1`` and
    ``zllm.reduction.ratio``):

    * ``cluster.family_f1`` — pairwise F1 of bit-distance clustering against
      ``families.json``, scored over the full-weight same-signature kinds
      (base / finetune / reupload / checkpoint). Vocab-expanded and
      quantized variants are excluded by design: they cross the shape or
      dtype signature, which defeats bit-distance on purpose — the store
      reaches them via declared metadata instead (see docs/EVALUATION.md).
    * ``reduction.ratio`` — the end-to-end stored-bytes reduction of the
      full pipeline over the whole corpus (the paper's headline ~54%
      hub-wide figure, scaled to the synthetic tier).
    """
    from repro.core.clustering import score_family_clustering
    from repro.core.bitdistance import DEFAULT_THRESHOLD

    kinds = {"base", "finetune", "reupload", "checkpoint"}
    scored = [(ctx.primary_file(rid), ctx.families[rid])
              for rid, kind in ctx.manifest if kind in kinds]
    paths, labels = zip(*scored)
    s = score_family_clustering(paths, labels)
    return {
        "cluster": {"family_f1": s["f1"], "family_precision": s["precision"],
                    "family_recall": s["recall"],
                    "pair_accuracy": s["accuracy"],
                    "n_models": s["n_models"], "n_clusters": s["n_clusters"],
                    "threshold_bits_per_elem": DEFAULT_THRESHOLD},
        "reduction": {"ratio": round(store.stats.reduction_ratio, 4)},
    }


def _thread_ceiling(n_threads: int, blob_kb: int = 512, reps: int = 48) -> float:
    """Measured speedup of pure GIL-releasing compression jobs across
    ``n_threads`` — the hardware ceiling any threaded engine can reach on
    this machine (containers with throttled/SMT-shared cores report well
    under n_threads; the engine's speedup should be read against this)."""
    import time
    from concurrent.futures import ThreadPoolExecutor
    rng = np.random.RandomState(0)
    blobs = [rng.bytes(blob_kb << 10) for _ in range(reps)]
    c = zstd.ZstdCompressor(level=3)
    t0 = time.perf_counter()
    for b in blobs:
        c.compress(b)
    t1 = time.perf_counter()
    with ThreadPoolExecutor(n_threads) as ex:
        list(ex.map(lambda b: zstd.ZstdCompressor(level=3).compress(b), blobs))
    t2 = time.perf_counter()
    return round((t1 - t0) / (t2 - t1), 2) if t2 > t1 else float("inf")


def workers_sweep(ctx: Ctx, workers=(1, 4)) -> dict:
    """Serial-vs-parallel zLLM engine on the same corpus.

    ``workers=1`` is the serial reference path; each parallel setting must
    produce bit-identical containers (asserted here on every sweep, and
    independently in tests/test_parallel_engine.py).
    """
    total = corpus_bytes(ctx)
    out: dict = {"hardware_thread_ceiling": _thread_ceiling(max(workers))}
    roots = {}
    for w in workers:
        root = f"/tmp/repro-bench-zllm-w{w}"
        shutil.rmtree(root, ignore_errors=True)
        roots[w] = root
        store = ZLLMStore(root, workers=w)
        with Timer() as t_in:
            for rid, _ in ctx.manifest:
                store.ingest_repo(ctx.repo_path(rid), rid)
        with Timer() as t_out:
            _retrieve_all(ctx, store)
        out[f"workers_{w}"] = {
            "ingest_MBps": _mbps(total, t_in.seconds),
            "retrieve_MBps": _mbps(total, t_out.seconds),
            "reduction_ratio": round(store.stats.reduction_ratio, 4),
            "base_map_cache": dict(store.base_map_stats),
        }
        store.close()

    # cross-file pipelined engine over the SAME corpus in one ingest_many
    # batch: must stay bit-identical to serial AND is the gated pipelined
    # ingest/retrieve figure. The index is saved so the serving bench can
    # front this store from a fresh process.
    proot = PIPELINED_STORE_ROOT
    shutil.rmtree(proot, ignore_errors=True)
    store = ZLLMStore(proot, workers=max(workers), pipeline_depth=2)
    # ingest_repos (NOT raw ingest_many over file paths): repo metadata must
    # be parsed exactly as in the serial per-repo sweep, or metadata-declared
    # bases (lora/vocab repos at default scale) silently resolve differently
    # and the bit-identity assertion below fails
    with Timer() as t_in:
        store.ingest_repos([(ctx.repo_path(rid), rid)
                            for rid, _ in ctx.manifest])
    with Timer() as t_out:
        _retrieve_all(ctx, store)
    out["pipelined"] = {
        "ingest_MBps": _mbps(total, t_in.seconds),
        "retrieve_MBps": _mbps(total, t_out.seconds),
        "reduction_ratio": round(store.stats.reduction_ratio, 4),
    }
    # scored family-accuracy + end-to-end reduction (CI-gated): computed on
    # the pipelined store, the same one the serving benches front
    out.update(family_scoring(ctx, store))
    store.save_index()
    store.close()

    # device-batched ingest leg (the gated zllm.ingest.device_batched_MBps
    # figure): same corpus through the backend "auto" resolves to on this
    # box — the batched jax/Pallas path on accelerator hosts, the numpy host
    # path on CPU-only boxes (so the gate measures "no regression when
    # falling back" there). Containers must stay bit-identical to serial.
    from repro.core.bitx import get_backend
    droot = "/tmp/repro-bench-zllm-device"
    shutil.rmtree(droot, ignore_errors=True)
    store = ZLLMStore(droot, workers=max(workers), backend="auto")
    with Timer() as t_in:
        for rid, _ in ctx.manifest:
            store.ingest_repo(ctx.repo_path(rid), rid)
    with Timer() as t_out:
        _retrieve_all(ctx, store)
    out["ingest"] = {
        "array_backend": store.backend.name,
        "device_batched_MBps": _mbps(total, t_in.seconds),
        "device_batched_retrieve_MBps": _mbps(total, t_out.seconds),
    }
    store.close()

    w0 = workers[0]
    for w in workers[1:]:
        _assert_identical_containers(roots[w0], roots[w])
    _assert_identical_containers(roots[w0], proot)
    _assert_identical_containers(roots[w0], droot)
    out["containers_bit_identical"] = True
    base = out[f"workers_{w0}"]["ingest_MBps"]
    best = max(out[f"workers_{w}"]["ingest_MBps"] for w in workers)
    out["ingest_speedup_best_vs_serial"] = round(best / base, 2) if base else 0.0

    # backend hot-path transform throughput (gated zllm.kernel.* keys)
    from benchmarks.bench_kernels import gated_hotpath
    out["kernel"] = gated_hotpath()
    return out


def two_upload_overlap(ctx: Ctx, workers: int = 4, repeats: int = 5) -> dict:
    """Acceptance metric: two uploads through the cross-file pipeline vs the
    sum of their serial per-file ingest times. The overlap hides upload B's
    FileDedup hashing + header parse under upload A's encode, and A's
    deferred container write under B's decisions; best-of-``repeats`` on
    both sides to cut scheduler noise."""
    picks = sorted(ctx.manifest,
                   key=lambda m: os.path.getsize(ctx.primary_file(m[0])),
                   reverse=True)[:2]
    uploads = [(ctx.primary_file(rid), rid) for rid, _ in picks]
    nbytes = sum(os.path.getsize(p) for p, _ in uploads)
    best_serial, serial_parts, best_wall = float("inf"), None, float("inf")
    for _ in range(repeats):
        root = "/tmp/repro-bench-overlap-serial"
        shutil.rmtree(root, ignore_errors=True)
        with ZLLMStore(root, workers=workers) as s:
            parts = []
            for p, rid in uploads:  # per-file calls cannot overlap each other
                with Timer() as t:
                    s.ingest_file(p, rid)
                parts.append(t.seconds)
        if sum(parts) < best_serial:
            best_serial, serial_parts = sum(parts), parts
        root = "/tmp/repro-bench-overlap-pipe"
        shutil.rmtree(root, ignore_errors=True)
        with ZLLMStore(root, workers=workers, pipeline_depth=2) as s:
            with Timer() as t:
                s.ingest_many(uploads)
        best_wall = min(best_wall, t.seconds)
    return {
        "uploads": [rid for _, rid in uploads],
        "serial_per_file_s": [round(x, 4) for x in serial_parts],
        "serial_sum_s": round(best_serial, 4),
        "overlapped_wall_s": round(best_wall, 4),
        "overlap_speedup": round(best_serial / best_wall, 3) if best_wall else 0.0,
        "wall_below_serial_sum": bool(best_wall < best_serial),
        "overlap_MBps": _mbps(nbytes, best_wall),
    }


def serving_bench(ctx: Ctx, store_root: str, concurrency: int = 8,
                  rounds: int = 3) -> dict:
    """Concurrent retrieval throughput through the async engine (the CI-gated
    serving figure): ``concurrency`` clients each sweep the corpus
    ``rounds`` times against a store loaded fresh from its index. The
    response cache is disabled (``cache_bytes=0``) and client sweeps are
    rotated so the figure measures concurrent *decodes*; only genuinely
    concurrent same-key requests coalesce (single-flight), which is the
    serving behavior under test."""
    from repro.serve.store_server import RetrievalEngine

    store = ZLLMStore(store_root, workers=2)
    assert store.load_index(), f"no index under {store_root}"
    reqs = [rid for rid, _ in ctx.manifest]

    async def client(engine, order):
        served = 0
        for rid in order:
            served += len(await engine.get_file(rid))
        return served

    async def run():
        engine = RetrievalEngine(store, max_concurrency=concurrency,
                                 cache_bytes=0, verify=False)
        try:
            orders = [(reqs[i % len(reqs):] + reqs[:i % len(reqs)]) * rounds
                      for i in range(concurrency)]
            t0 = time.perf_counter()
            served = await asyncio.gather(*(client(engine, o) for o in orders))
            wall = time.perf_counter() - t0
            return sum(served), wall, engine.stats()
        finally:
            await engine.aclose()

    served, wall, stats = asyncio.run(run())
    store.close()
    return {
        "concurrency": concurrency,
        "rounds": rounds,
        "served_MB": round(served / 2**20, 1),
        "concurrent_retrieve_MBps": _mbps(served, wall),
        "singleflight": stats["singleflight"],
    }


def http_serving_bench(ctx: Ctx, store_root: str, small_reqs: int = 300,
                       range_kb: int = 64) -> dict:
    """The HTTP/1.1 protocol figures gated in CI (PR 5's serving layer):

    * ``keepalive_reqs_per_s`` — small ranged GETs fired back-to-back on
      ONE persistent connection; after the first request the object is in
      the response cache, so this measures pure request plumbing
      (parse → route → slice → respond) with connection reuse.
    * ``range_read_MBps`` — a cold-start-loader sweep: the largest file
      fetched as consecutive ``range_kb``-KB ``Range:`` slices over a
      keep-alive connection (decode-once; slices cut from the cached
      buffer, ``stored`` frames via sendfile).
    """
    import http.client

    from repro.serve.store_server import ServerThread

    store = ZLLMStore(store_root, workers=2)
    assert store.load_index(), f"no index under {store_root}"
    target = max((rid for rid, _ in ctx.manifest),
                 key=lambda rid: os.path.getsize(ctx.primary_file(rid)))
    target_file = os.path.basename(ctx.primary_file(target))
    size = os.path.getsize(ctx.primary_file(target))
    out: dict = {}
    try:
        with ServerThread(store, max_concurrency=4) as srv:
            conn = http.client.HTTPConnection(srv.host, srv.port, timeout=60)
            path = f"/repo/{target}/file/{target_file}"

            def ranged(lo: int, hi: int) -> int:  # [lo, hi) -> bytes served
                conn.request("GET", path,
                             headers={"Range": f"bytes={lo}-{hi - 1}"})
                r = conn.getresponse()
                body = r.read()
                assert r.status == 206, r.status
                return len(body)

            ranged(0, 1024)  # warm the response cache (one decode)
            t0 = time.perf_counter()
            for i in range(small_reqs):
                off = (i * 4096) % max(1, size - 1024)
                ranged(off, off + 1024)
            t_small = time.perf_counter() - t0

            chunk = range_kb << 10
            swept = 0
            t0 = time.perf_counter()
            for lo in range(0, size, chunk):
                swept += ranged(lo, min(lo + chunk, size))
            t_sweep = time.perf_counter() - t0
            server_http = dict(srv.server.http)
            conn.close()
    finally:
        store.close()
    assert server_http["connections"] == 1, "keep-alive reuse broke"
    out["keepalive_reqs_per_s"] = round(small_reqs / t_small, 1) \
        if t_small > 0 else float("inf")
    out["keepalive_small_reqs"] = small_reqs
    out["range_read_MBps"] = _mbps(swept, t_sweep)
    out["range_read_slices"] = (size + chunk - 1) // chunk
    out["range_slice_kb"] = range_kb
    return out


def compaction_bench(ctx: Ctx, workers: int = 2) -> dict:
    """Churn workload for the lifecycle metrics gated in CI: build a
    dedup-chain of partial re-registrations over the corpus's largest base
    (stranding dead payloads in superseded generations), delete the
    fine-tune repos, sweep with the *incremental* collector (recording its
    max exclusive read-gate pause), then ``compact()`` — reporting the net
    bytes reclaimed and the reclaim ratio against the superseded total.
    Every surviving file is verified bit-exact afterwards."""

    root = "/tmp/repro-bench-compaction"
    scratch = "/tmp/repro-bench-compaction-chain"
    shutil.rmtree(root, ignore_errors=True)
    shutil.rmtree(scratch, ignore_errors=True)
    with ZLLMStore(root, workers=workers) as store:
        for rid, _ in ctx.manifest:
            store.ingest_repo(ctx.repo_path(rid), rid)
        base_rid = next(rid for rid, kind in ctx.manifest if kind == "base")
        prev = os.path.join(scratch, "g0", "model.safetensors")
        chain_copy(ctx.primary_file(base_rid), prev, seed=31, residue=None)
        store.ingest_file(prev, "bench-compact/base")
        for r in range(3):
            p = os.path.join(scratch, f"g{r + 1}", "model.safetensors")
            chain_copy(prev, p, seed=32 + r, residue=r)
            store.ingest_file(p, "bench-compact/base")
            prev = p
        chain_bytes = open(prev, "rb").read()
        for rid, kind in ctx.manifest:
            if kind == "finetune":
                store.delete_repo(rid)
        with Timer() as t_gc:
            swept = store.gc(incremental=True, max_pause_ms=50.0)
        superseded = store.summary()["lifecycle"]["superseded_bytes"]
        with Timer() as t_c:
            rep = store.compact()
        assert store.retrieve_file("bench-compact/base",
                                   "model.safetensors") == chain_bytes
        assert store.fsck(spot_check=1).ok
        return {
            "superseded_bytes": superseded,
            "compaction_reclaimed_bytes": rep["net_reclaimed_bytes"],
            "compaction_reclaim_ratio": round(
                rep["net_reclaimed_bytes"] / superseded, 4) if superseded else 0.0,
            "compaction_moved_records": rep["moved_records"],
            "compaction_exclusive_hold_ms": rep["exclusive_hold_ms"],
            "compaction_wall_s": round(t_c.seconds, 4),
            "incremental_gc_max_pause_ms": swept["max_pause_ms"],
            "incremental_gc_steps": swept["steps"],
            "incremental_gc_collected": swept["collected"],
            "incremental_gc_wall_s": round(t_gc.seconds, 4),
        }


def loadgen_bench(ctx: Ctx, store_root: str) -> dict:
    """Read-path tail latency + conditional-GET revalidation ratio via the
    ``server_smoke`` multi-process load-generator leg, fronting the same
    pipelined store the other serving benches use (CI-gated
    ``serving.p99_ms`` lower-is-better / ``serving.conditional_hit_ratio``
    higher-is-better). The leg's correctness assertions (byte-identical
    full GETs, bodiless 304s, stable validators under read-only load)
    must hold or the bench aborts."""
    from benchmarks.server_smoke import loadgen_leg

    failures, metrics = loadgen_leg(ctx, store_root=store_root)
    assert not failures, f"loadgen leg failed: {failures[:3]}"
    return metrics


def replication_bench(ctx: Ctx) -> dict:
    """Replicated-tier figures (3 roots, replicas=3, W=2) via the
    ``server_smoke`` replica leg — sync quorum-PUT p99 latency, read
    throughput through failover with one root down, and the wall time of
    the anti-entropy sweep that converges the restarted root. The leg's
    correctness assertions (zero failed reads, byte-identity, empty index
    diff) must hold or the bench aborts. The peer chaos leg then runs the
    same coordinator against two HTTP peers behind a chaos proxy and
    folds in the cross-process figures — targeted hint-drain wall time
    and the anti-entropy wire-shipping throughput of a dead-node swap
    (``hint_drain_s``, ``peer_ship_MBps``)."""
    from benchmarks.server_smoke import peer_chaos_leg, replica_leg

    failures, metrics = replica_leg(ctx)
    assert not failures, f"replica leg failed: {failures[:3]}"
    p_failures, p_metrics = peer_chaos_leg(ctx)
    assert not p_failures, f"peer chaos leg failed: {p_failures[:3]}"
    metrics.update(p_metrics)
    return metrics


def _assert_identical_containers(root_a: str, root_b: str) -> None:
    ca, cb = os.path.join(root_a, "containers"), os.path.join(root_b, "containers")
    for dirpath, _, files in os.walk(ca):
        for fn in files:
            pa = os.path.join(dirpath, fn)
            pb = os.path.join(cb, os.path.relpath(pa, ca))
            assert open(pa, "rb").read() == open(pb, "rb").read(), \
                f"parallel container diverged from serial: {pb}"


def run(ctx: Ctx, workers=(1, 4)) -> dict:
    total = corpus_bytes(ctx)
    out = {"corpus_MB": round(total / 2**20, 1), "entropy_backend": ENTROPY_BACKEND}

    # --- zstd baseline (compression only) -------------------------------
    c = zstd.ZstdCompressor(level=3)
    d = zstd.ZstdDecompressor()
    frames = []
    with Timer() as t_in:
        for rid, _ in ctx.manifest:
            for path in ctx.repo_files(rid):
                frames.append(c.compress(open(path, "rb").read()))
    with Timer() as t_out:
        for f in frames:
            d.decompress(f)
    out["zstd"] = {"ingest_MBps": _mbps(total, t_in.seconds),
                   "retrieve_MBps": _mbps(total, t_out.seconds),
                   "reduction_ratio": round(1 - sum(len(f) for f in frames) / total, 4)}

    # --- HF-style ChunkDedup (FastCDC, no compression) -------------------
    cd = ChunkDedup(FastCDC(min_size=4096, avg_size=16384, max_size=65536))
    with Timer() as t_cdc:
        for rid, _ in ctx.manifest:
            for path in ctx.repo_files(rid):
                cd.scan_file(path)
    out["hf_fastcdc"] = {"ingest_MBps": _mbps(total, t_cdc.seconds),
                         "retrieve_MBps": "line-rate",
                         "reduction_ratio": round(cd.stats.reduction_ratio, 4)}

    # --- ZipNN + FileDedup (no cross-model delta) ------------------------
    root = "/tmp/repro-bench-zipnn-store"
    shutil.rmtree(root, ignore_errors=True)
    s_zipnn = ZLLMStore(root, use_bitx=False, use_tensor_dedup=False)
    with Timer() as t_in:
        for rid, _ in ctx.manifest:
            s_zipnn.ingest_repo(ctx.repo_path(rid), rid)
    with Timer() as t_out:
        _retrieve_all(ctx, s_zipnn)
    out["zipnn_filededup"] = {"ingest_MBps": _mbps(total, t_in.seconds),
                              "retrieve_MBps": _mbps(total, t_out.seconds),
                              "reduction_ratio": round(s_zipnn.stats.reduction_ratio, 4)}
    s_zipnn.close()

    # --- zLLM (full pipeline): serial-vs-parallel engine sweep -----------
    out["zllm"] = workers_sweep(ctx, workers)

    # --- cross-file pipelining + concurrent serving (PR 3) ---------------
    out["pipelined_two_uploads"] = two_upload_overlap(ctx, workers=max(workers))
    out["serving"] = serving_bench(ctx, PIPELINED_STORE_ROOT)
    # --- HTTP keep-alive + range-read protocol figures (PR 5) ------------
    out["serving"].update(http_serving_bench(ctx, PIPELINED_STORE_ROOT))
    # --- multi-process conditional-GET load (PR 9): serving.p99_ms
    # lower-is-better, serving.conditional_hit_ratio higher-is-better ----
    out["serving"].update(loadgen_bench(ctx, PIPELINED_STORE_ROOT))

    # --- compaction + incremental GC (PR 4): the CI-gated lifecycle
    # metrics (compaction_reclaimed_bytes higher-is-better,
    # incremental_gc_max_pause_ms lower-is-better) ------------------------
    out["lifecycle_compaction"] = compaction_bench(ctx)

    # --- replicated tier (PR 6): the quorum-write / read-failover /
    # anti-entropy figures, produced by the server_smoke acceptance leg so
    # the gated numbers come from the same code path CI proves correct.
    # failover_read_MBps gates higher-is-better; quorum_put_p99_ms and
    # anti_entropy_repair_s gate lower-is-better (rise-gated) -------------
    out["replication"] = replication_bench(ctx)

    serial = out["zllm"][f"workers_{workers[0]}"]
    out["relative_ordering_ok"] = bool(
        out["hf_fastcdc"]["ingest_MBps"] < out["zipnn_filededup"]["ingest_MBps"]
        and serial["ingest_MBps"] > 0.5 * out["zipnn_filededup"]["ingest_MBps"])
    return out


def main() -> None:
    import argparse
    from benchmarks.common import build_ctx

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--scale", default="default",
                    choices=["tiny", "small", "default", "large", "hub"])
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke: seconds-scale corpus (alias for --scale tiny)")
    ap.add_argument("--hub-scale", action="store_true",
                    help="paper-§4.2-shaped hub tier (alias for --scale hub)")
    def workers_list(text: str):
        try:
            out = tuple(int(w) for w in text.split(","))
        except ValueError:
            raise argparse.ArgumentTypeError(
                f"expected comma-separated integers, got {text!r}")
        if not out or any(w < 1 for w in out):
            raise argparse.ArgumentTypeError(f"worker counts must be >= 1: {text!r}")
        return out

    ap.add_argument("--workers", default=(1, 4), type=workers_list,
                    help="comma-separated worker counts; first entry is the serial reference")
    args = ap.parse_args()
    scale = "tiny" if args.tiny else "hub" if args.hub_scale else args.scale
    emit("throughput", run(build_ctx(scale), args.workers))


if __name__ == "__main__":
    main()
