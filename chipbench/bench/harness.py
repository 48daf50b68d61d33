"""One run of one cell: set-up, a measured window, the check of every answer.

The system under test is the store server through its normal entry:
``ServerThread`` over ``ZLLMStore(backend="jax")`` with one root and the
server's defaults (``--store-workers 2``, 8 serve workers, a 128 MB response
cache with a 512 MB spill tier, sha256 verification on). The load comes from
client processes (``bench/client.py``) that never import JAX, so this process
alone holds the chip and can trace it.

The traffic is a closed loop of ``clients`` uploaders. In set-up each makes
``uploads_per_client`` fine-tunes of the base; from the window's start each
sends its next one with ``PUT ?sync=1&base=`` as soon as its last one is
acknowledged, until the window closes or it has none left. After the window
every acknowledged upload is read back whole and compared segment by segment
with the generator's sha256.
"""

from __future__ import annotations

import http.client
import json
import os
import resource
import shutil
import subprocess
import sys
import time
from typing import Callable, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
CLIENT = os.path.join(HERE, "client.py")


class ClientProc:
    """One client process and its command pipe."""

    def __init__(self, index: int):
        self.index = index
        self.proc = subprocess.Popen(
            [sys.executable, CLIENT], stdin=subprocess.PIPE,
            stdout=subprocess.PIPE, text=True, bufsize=1)

    def send(self, **cmd) -> None:
        self.proc.stdin.write(json.dumps(cmd) + "\n")
        self.proc.stdin.flush()

    def recv(self) -> Dict:
        line = self.proc.stdout.readline()
        if not line:
            raise RuntimeError(f"client {self.index} exited "
                               f"(code {self.proc.poll()})")
        return json.loads(line)

    def ask(self, **cmd) -> Dict:
        self.send(**cmd)
        return self.recv()

    def close(self) -> None:
        try:
            if self.proc.poll() is None:
                self.send(op="exit")
                self.proc.stdin.close()
                self.proc.wait(timeout=60)
        except (OSError, subprocess.TimeoutExpired):
            pass
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()


def all_ask(clients: List[ClientProc], cmds: List[Dict]) -> List[Dict]:
    """Send one command to each client, then collect every answer."""
    for c, cmd in zip(clients, cmds):
        c.send(**cmd)
    return [c.recv() for c in clients]


def http_json(host: str, port: int, path: str) -> Dict:
    conn = http.client.HTTPConnection(host, port, timeout=120)
    try:
        conn.request("GET", path)
        r = conn.getresponse()
        body = r.read()
    finally:
        conn.close()
    if r.status != 200:
        raise RuntimeError(f"GET {path}: HTTP {r.status}")
    return json.loads(body)


def store_bytes(root: str) -> int:
    """Bytes on disk under a store root, leaving out the upload spool and
    the response cache's spill tier (neither holds stored data)."""
    total = 0
    for d, dirs, files in os.walk(root):
        dirs[:] = [x for x in dirs if x not in (".spool", ".decoded")]
        for f in files:
            try:
                total += os.path.getsize(os.path.join(d, f))
            except OSError:
                pass
    return total


class Run:
    """State of one run, handed to the metric readers."""

    def __init__(self, cell: Dict, config: Dict, traffic: Dict, seed: int,
                 seconds: float):
        self.cell, self.config, self.traffic = cell, config, traffic
        self.seed, self.seconds = int(seed), float(seconds)
        self.t_start = time.monotonic()
        self.t0 = self.t_end = self.t_drain = 0.0
        self.t_span = 0.0           # when the traced window span opened
        self.records: List[Dict] = []
        self.stats0: Dict = {}
        self.stats1: Dict = {}
        self.root = ""
        self.stored0 = self.stored1 = 0   # bytes under the store root
        self.compiles: List[float] = []
        self.trace = None           # bench.trace.Reduced, traced runs only
        self.peaks: Dict = {}
        self.checks: Dict[str, Dict] = {}
        self.notes: Dict = {}

    @property
    def setup_s(self) -> float:
        return self.t0 - self.t_start


def run_cell(cell: Dict, config: Dict, traffic: Dict, seed: int,
             seconds: float, workdir: str, *, trace_dir: Optional[str] = None,
             patch: Optional[Callable] = None, log=print,
             gen_threads: int = 6, t_start: Optional[float] = None) -> Run:
    """Set up, measure for ``seconds``, check; returns the finished run.
    ``patch(store)``, where given, changes the program before any upload
    (the control and the fault tests use it)."""
    import jax
    from repro.core.pipeline import ZLLMStore
    from repro.serve.store_server import ServerThread

    run = Run(cell, config, traffic, seed, seconds)
    if t_start is not None:
        run.t_start = t_start
    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, *_a, **_k: run.compiles.append(time.monotonic())
        if ev == "/jax/core/compile/backend_compile_duration" else None)
    jax.monitoring.register_event_listener(
        lambda ev, *_a, **_k: run.compiles.append(time.monotonic())
        if ev == "/jax/compilation_cache/cache_hits" else None)

    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir)
    run.root = os.path.join(workdir, "store")
    store = ZLLMStore(run.root, backend="jax", workers=2)
    clients: List[ClientProc] = []
    srv = None
    own = set(vars(store.backend))  # the backend object is shared process-wide
    try:
        if store.summary()["array_backend"] != "jax":
            raise RuntimeError("the store does not run the jax backend")
        if patch is not None:
            patch(store)
        srv = ServerThread(store).start()
        n = int(traffic.get("clients", 2))
        clients = [ClientProc(i) for i in range(n)]
        all_ask(clients, [dict(op="init", host=srv.host, port=srv.port,
                               config=config, traffic=traffic, seed=seed,
                               threads=gen_threads) for _ in clients])
        log(f"clients ready after {time.monotonic() - run.t_start:.1f}s")
        _ingest(run, srv, clients, f"{cell['config']}-base", trace_dir, log)
        stats = jax.devices()[0].memory_stats() or {}
        run.notes["memory_peak_bytes"] = stats.get("peak_bytes_in_use")
        _check(run, clients, log)
    finally:
        for c in clients:
            c.close()
        if srv is not None:
            srv.stop()
        store.close()
        for name in set(vars(store.backend)) - own:
            delattr(store.backend, name)   # undo ``patch``
        shutil.rmtree(workdir, ignore_errors=True)
    run.notes["max_rss_mb"] = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss >> 10
    return run


def _window(run: Run, srv, trace_dir: Optional[str], body: Callable[[], None]):
    """Read /stats, open the window (traced when asked), run ``body``
    until every request it sent has been answered, read /stats again."""
    import jax
    run.stats0 = http_json(srv.host, srv.port, "/stats")
    run.stored0 = store_bytes(run.root)
    if trace_dir:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0   # the server is Python: trace only spans
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("chipbench.window"):
            run.t_span = time.monotonic()
            body()
    finally:
        if trace_dir:
            jax.profiler.stop_trace()
    run.stats1 = http_json(srv.host, srv.port, "/stats")
    run.stored1 = store_bytes(run.root)


def _ingest(run: Run, srv, clients, base, trace_dir, log) -> None:
    """Upload the base and one warm-up fine-tune while the clients make
    their fine-tunes for the window, then run the window."""
    n, k = len(clients), int(run.traffic["uploads_per_client"])
    fmt = f"{run.cell['config']}-ft-{{}}"
    # stream 1 is the warm-up; client c sends streams 2 + c, 2 + c + n, ...
    pools = [[2 + c + n * i for i in range(k)] for c in range(n)]
    for c in clients[1:]:
        c.send(op="prepare", streams=pools[c.index])
    rec = clients[0].ask(op="put", stream=0, repo=base)
    if rec["status"] != 200:
        raise RuntimeError(f"base upload failed: {rec}")
    log(f"base {rec['bytes']} bytes acknowledged in "
        f"{rec['t_ack'] - rec['t_send']:.1f}s, row {json.dumps(rec['row'])}")
    # warm-up: one upload compiles every shape, since all uploads of a
    # configuration hold the same tensors
    warm = clients[0].ask(op="put", stream=1, repo=fmt.format(1), base=base)
    if warm["status"] != 200:
        raise RuntimeError(f"warm-up upload failed: {warm}")
    log(f"warm-up upload acknowledged in {warm['t_ack'] - warm['t_send']:.1f}s")
    clients[0].ask(op="prepare", streams=pools[0])
    for c in clients[1:]:
        c.recv()
    log(f"{n} x {k} uploads made after {time.monotonic() - run.t_start:.1f}s")

    def body():
        run.t0 = time.monotonic() + 0.05
        run.t_end = run.t0 + run.seconds
        outs = all_ask(clients, [dict(op="ingest", t0=run.t0, t_end=run.t_end,
                                      base=base, repo_fmt=fmt) for _ in clients])
        run.records = [dict(r, client=c) for c, o in enumerate(outs)
                       for r in o["records"]]
        # every upload sent has been answered: the window ends here, also
        # where the clients ran out of uploads before ``t_end``
        run.t_drain = time.monotonic()

    _window(run, srv, trace_dir, body)


def correct(checks: Dict[str, Dict]) -> bool:
    """Every number compared is within its limit."""
    return all(c["value"] <= c["limit"] if c["rule"] == "<="
               else c["value"] >= c["limit"] for c in checks.values())


def lossy_control(store) -> None:
    """The control: the store drops the lowest bit of every XOR delta, as a
    lossy codec would. Reads then differ from uploads, and a run with it
    must come out not correct."""
    encode = store.backend.xor_delta_planes_batch

    def dropped(pairs):
        out = encode(pairs)
        for planes in out:
            planes[-1] = planes[-1] & 0xFE
        return out
    store.backend.xor_delta_planes_batch = dropped


def _check(run: Run, clients: List[ClientProc], log) -> None:
    """Compare what the window produced with the generator's reference."""
    recs = run.records
    failed = sum(1 for r in recs if r["status"] != 200)
    run.checks["failed_requests"] = {"value": failed, "limit": 0, "rule": "<="}
    acked = [r for r in recs if r["status"] == 200]
    repos: Dict[int, List[str]] = {c.index: [] for c in clients}
    for r in acked:
        repos[r["client"]].append(r["repo"])
    t = time.monotonic()
    outs = all_ask(clients, [dict(op="verify", repos=repos[c.index])
                             for c in clients])
    res = [x for o in outs for x in o["results"]]
    log(f"read back {len(res)} acknowledged uploads in "
        f"{time.monotonic() - t:.1f}s")
    for x in res:
        if not x["ok"]:
            log(f"upload {x['repo']} differs: {json.dumps(x)}")
    run.checks["upload_mismatch"] = {"value": sum(1 for x in res if not x["ok"]),
                                     "limit": 0, "rule": "<="}
    run.checks["uploads_read_back"] = {"value": len(res), "limit": 1, "rule": ">="}

