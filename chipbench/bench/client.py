"""A load-generator process: uploads over HTTP and reads them back, JAX-free.

    python3 chipbench/bench/client.py

The harness starts each client as a process of its own and drives it with
one JSON command per line on standard input; each answer is one JSON line on
standard output. Times are ``time.monotonic()``, one clock for every process
of the machine. A client keeps the digests of every upload it sent, so that
it can check what the store gives back.

Commands (``op``):

- ``init``: make the base from the seed.
- ``put``: upload one stream (0 is the base) with ``?sync=1``.
- ``prepare``: make the fine-tunes of ``streams`` and hold them, so that
  nothing is generated while the window runs.
- ``ingest``: closed loop from ``t0``: upload the next prepared fine-tune as
  soon as the last is acknowledged, until ``t_end`` or until none is left.
- ``verify``: GET whole files back and compare every segment's sha256.
- ``exit``.
"""

from __future__ import annotations

import hashlib
import http.client
import json
import os
import sys
import time
from typing import Dict, List, Optional

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench.gen import Generator, Upload  # noqa: E402

FILENAME = "model.safetensors"
_READ = 8 << 20


class Client:
    def __init__(self, host: str, port: int, timeout: float = 900.0):
        self.host, self.port, self.timeout = host, port, timeout
        self.gen: Optional[Generator] = None
        self.ready: List[tuple] = []   # (stream, Upload) made by ``prepare``
        # repo id -> (segment sizes, sha256 of each, names) of what was sent
        self.sent: Dict[str, tuple] = {}

    def _conn(self) -> http.client.HTTPConnection:
        return http.client.HTTPConnection(self.host, self.port,
                                          timeout=self.timeout)

    # -- uploads ------------------------------------------------------------
    def put(self, repo: str, up: Upload, base: Optional[str]) -> Dict:
        """PUT ?sync=1; the record says when it was sent and acknowledged."""
        path = f"/repo/{repo}/file/{FILENAME}?sync=1"
        if base:
            path += f"&base={base}"
        rec = {"repo": repo, "bytes": up.nbytes, "t_send": time.monotonic()}
        conn = self._conn()
        try:
            conn.putrequest("PUT", path)
            conn.putheader("Content-Length", str(up.nbytes))
            conn.endheaders()
            for seg in up.segments:
                conn.send(seg)
            r = conn.getresponse()
            body = r.read()
            rec["status"] = r.status
            if r.status == 200:
                job = json.loads(body).get("job") or {}
                rows = job.get("results") or [{}]
                rec["row"] = rows[0]
                if job.get("state") != "done":
                    rec["status"] = -1
            else:
                rec["error"] = body[:300].decode("latin-1")
        except (OSError, http.client.HTTPException, ValueError) as e:
            rec["status"], rec["error"] = -1, f"{type(e).__name__}: {e}"
        finally:
            conn.close()
        rec["t_ack"] = time.monotonic()
        if rec["status"] == 200:
            self.sent[repo] = ([s.nbytes for s in up.segments], up.digests,
                               up.names)
        return rec

    def prepare(self, streams: List[int]) -> None:
        self.ready = [(s, self.gen.upload(s)) for s in streams]

    def ingest(self, t0: float, t_end: float, repo_fmt: str,
               base: str) -> List[Dict]:
        """Closed loop over the prepared fine-tunes, from ``t0``."""
        now = time.monotonic()
        if now < t0:
            time.sleep(t0 - now)
        out = []
        while self.ready and time.monotonic() < t_end:
            s, up = self.ready.pop(0)
            rec = self.put(repo_fmt.format(s), up, base)
            rec["stream"] = s
            rec["changed_bytes"] = self.gen.changed_bytes
            out.append(rec)
        self.ready = []
        return out

    # -- reads ----------------------------------------------------------------
    def verify(self, repo: str) -> Dict:
        """GET the whole file; compare each segment with what was sent."""
        res = {"repo": repo, "ok": False}
        if repo not in self.sent:
            res["error"] = "never acknowledged by this client"
            return res
        sizes, digests, names = self.sent[repo]
        bounds, pos = [], 0
        for size in sizes:
            bounds.append((pos, pos + size))
            pos += size
        conn = self._conn()
        try:
            conn.request("GET", f"/repo/{repo}/file/{FILENAME}")
            r = conn.getresponse()
            if r.status != 200:
                res["error"] = f"HTTP {r.status}"
                r.read()
                return res
            seg, h, got = 0, hashlib.sha256(), 0
            bad = []
            buf = bytearray(_READ)
            while True:
                n = r.readinto(buf)
                if not n:
                    break
                view, i = memoryview(buf)[:n], 0
                while i < n and seg < len(bounds):
                    take = min(n - i, bounds[seg][1] - (got + i))
                    h.update(view[i:i + take])
                    i += take
                    if got + i == bounds[seg][1]:
                        if h.hexdigest() != digests[seg]:
                            bad.append(names[seg] or "header")
                        seg, h = seg + 1, hashlib.sha256()
                got += n
            res["bytes"] = got
            res["bad_segments"] = bad
            res["ok"] = got == pos and not bad
            if got != pos:
                res["error"] = f"{got} bytes, sent {pos}"
        except (OSError, http.client.HTTPException) as e:
            res["error"] = f"{type(e).__name__}: {e}"
        finally:
            conn.close()
        return res


def serve(stdin, stdout) -> None:
    cli: Optional[Client] = None
    for line in stdin:
        cmd = json.loads(line)
        op = cmd["op"]
        t = time.monotonic()
        if op == "exit":
            break
        if op == "init":
            cli = Client(cmd["host"], cmd["port"])
            cli.gen = Generator(cmd["config"], cmd["traffic"], cmd["seed"],
                                threads=cmd.get("threads", 4))
            out = {"base_bytes": cli.gen.upload(0).nbytes}
        elif op == "put":
            out = cli.put(cmd["repo"], cli.gen.upload(cmd["stream"]), cmd.get("base"))
        elif op == "prepare":
            cli.prepare(cmd["streams"])
            out = {}
        elif op == "ingest":
            out = {"records": cli.ingest(cmd["t0"], cmd["t_end"],
                                         cmd["repo_fmt"], cmd["base"])}
            # the window is the generator's last use: free the base before
            # the read-back, which needs only the digests
            cli.gen.close()
            cli.gen = None
        elif op == "verify":
            out = {"results": [cli.verify(r) for r in cmd["repos"]]}
        else:
            raise ValueError(f"unknown op {op!r}")
        out["secs"] = time.monotonic() - t
        stdout.write(json.dumps(out) + "\n")
        stdout.flush()
    if cli is not None and cli.gen is not None:
        cli.gen.close()


if __name__ == "__main__":
    serve(sys.stdin, sys.stdout)
