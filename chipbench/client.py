"""The load generator: a child process that never imports JAX.

    python3 -m chipbench.client <plan.json> <records.jsonl>

stdlib only (HTTP over loopback, threads). The plan is written by the
benchmark process: `base` URL, `requests` (method, path, headers,
a base64 `body`), and one `phase`:

  {"loop": "open", "due": [[request index, seconds], ...]}
      each request is sent at its due time whatever the server does;
      latency is timed from when it was due, and how late it was sent is
      recorded, so that a starved generator is not read as a fast server;
      a request more than GIVE_UP_S late is recorded as failed, unsent
  {"loop": "closed", "clients": n, "seconds": s, "order": [[...] per client]}
      each client sends its next request when the previous one has
      answered, until `seconds` have passed
  {"loop": "burst", "bursts": [[request index, ...], ...]}
      the requests of one burst are released together from a barrier;
      the next burst starts when all have answered

One record per request goes to the output, in JSON lines:
`i` (request index), `due`, `sent`, `done` (seconds from the phase's
start), `status` (0 = transport error), `body` (base64). The last line
is a summary with `jax_imported` (must be false) and lateness.
"""

from __future__ import annotations

import base64
import http.client
import json
import statistics
import sys
import threading
import time
from urllib.parse import urlparse

TIMEOUT_S = 60.0
GIVE_UP_S = 15.0      # an open-loop request this late is not sent any more


def send(host: str, port: int, req: dict) -> tuple[int, bytes]:
    conn = http.client.HTTPConnection(host, port, timeout=TIMEOUT_S)
    try:
        body = base64.b64decode(req["body"]) if req.get("body") else None
        conn.request(req["method"], req["path"], body=body,
                     headers=req.get("headers") or {})
        r = conn.getresponse()
        return r.status, r.read()
    except (OSError, http.client.HTTPException) as e:
        return 0, repr(e).encode()
    finally:
        conn.close()


class Recorder:
    def __init__(self, host, port, requests, t0):
        self.host, self.port, self.requests, self.t0 = host, port, requests, t0
        self.records: list[dict] = []
        self.lock = threading.Lock()

    def now(self) -> float:
        return time.perf_counter() - self.t0

    def skip(self, i: int, due: float) -> None:
        """Every sender is stuck behind a server that has fallen far
        behind: the request counts as failed, unsent, so that a collapsed
        step ends in minutes, not in (requests / senders) time-outs."""
        now = self.now()
        with self.lock:
            self.records.append({
                "i": i, "due": due, "sent": now, "done": now, "status": 0,
                "body": base64.b64encode(b"not sent: too late").decode()})

    def fire(self, i: int, due: float) -> None:
        sent = self.now()
        status, body = send(self.host, self.port, self.requests[i])
        done = self.now()
        with self.lock:
            self.records.append({
                "i": i, "due": due, "sent": sent, "done": done,
                "status": status,
                "body": base64.b64encode(body).decode()})


def run_open(rec: Recorder, due: list, threads: int) -> None:
    """`threads` senders share the schedule: each takes the next request,
    sleeps until it is due, sends it. With every sender busy the next
    request goes out late, which its `sent - due` shows."""
    nxt = [0]
    lock = threading.Lock()

    def worker():
        while True:
            with lock:
                k = nxt[0]
                nxt[0] += 1
            if k >= len(due):
                return
            i, t = due[k]
            wait = t - rec.now()
            if wait > 0:
                time.sleep(wait)
            if wait < -GIVE_UP_S:
                rec.skip(i, t)
            else:
                rec.fire(i, t)

    _run_threads([threading.Thread(target=worker, daemon=True)
                  for _ in range(min(threads, max(1, len(due))))])


def run_closed(rec: Recorder, order: list, seconds: float) -> None:
    def worker(seq):
        for i in seq:
            t = rec.now()
            if t >= seconds:
                return
            rec.fire(i, t)

    _run_threads([threading.Thread(target=worker, args=(seq,), daemon=True)
                  for seq in order])


def run_bursts(rec: Recorder, bursts: list) -> None:
    for burst in bursts:
        barrier = threading.Barrier(len(burst))

        def worker(i, barrier=barrier):
            barrier.wait()
            rec.fire(i, rec.now())

        _run_threads([threading.Thread(target=worker, args=(i,), daemon=True)
                      for i in burst])


def _run_threads(ts: list) -> None:
    for t in ts:
        t.start()
    for t in ts:
        t.join()


def main(argv: list[str]) -> int:
    plan_path, out_path = argv
    with open(plan_path) as f:
        plan = json.load(f)
    u = urlparse(plan["base"])
    phase = plan["phase"]
    rec = Recorder(u.hostname, u.port, plan["requests"], time.perf_counter())
    if phase["loop"] == "open":
        run_open(rec, phase["due"], int(phase.get("threads", 64)))
    elif phase["loop"] == "closed":
        run_closed(rec, phase["order"], float(phase["seconds"]))
    elif phase["loop"] == "burst":
        run_bursts(rec, phase["bursts"])
    else:
        raise SystemExit(f"unknown loop {phase['loop']!r}")
    late = [r["sent"] - r["due"] for r in rec.records] or [0.0]
    summary = {
        "summary": True, "jax_imported": "jax" in sys.modules,
        "requests": len(rec.records),
        "late_median_s": statistics.median(late), "late_max_s": max(late),
        "elapsed_s": rec.now()}
    with open(out_path, "w") as f:
        for r in sorted(rec.records, key=lambda r: r["due"]):
            f.write(json.dumps(r) + "\n")
        f.write(json.dumps(summary) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
