"""The open-loop scheduler times from due time and reports lateness."""

import base64
import json
import os
import subprocess
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


class Slow(BaseHTTPRequestHandler):
    def do_GET(self):  # noqa: N802
        time.sleep(0.2)
        body = self.path.encode()
        self.send_response(200)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def log_message(self, *a):
        pass


def run_client(tmp_path, phase, requests):
    srv = ThreadingHTTPServer(("127.0.0.1", 0), Slow)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    try:
        plan, out = tmp_path / "plan.json", tmp_path / "out.jsonl"
        plan.write_text(json.dumps({
            "base": f"http://127.0.0.1:{srv.server_address[1]}",
            "requests": requests, "phase": phase}))
        p = subprocess.run([sys.executable, "-m", "chipbench.client",
                            str(plan), str(out)], cwd=ROOT, timeout=60)
        assert p.returncode == 0
        rows = [json.loads(x) for x in out.read_text().splitlines()]
        return rows[:-1], rows[-1]
    finally:
        srv.shutdown()
        srv.server_close()


def test_open_loop_times_from_due_and_reports_lateness(tmp_path):
    reqs = [{"method": "GET", "path": f"/r{i}", "headers": {}}
            for i in range(4)]
    # one sender, a 0.2 s server, four requests due 0.05 s apart: the
    # second to fourth go out late, and their latency counts the wait
    phase = {"loop": "open", "threads": 1,
             "due": [[i, 0.05 * i] for i in range(4)]}
    rows, summary = run_client(tmp_path, phase, reqs)
    assert summary["jax_imported"] is False
    assert [r["i"] for r in rows] == [0, 1, 2, 3]
    assert all(r["status"] == 200 for r in rows)
    assert base64.b64decode(rows[2]["body"]) == b"/r2"
    late = [r["sent"] - r["due"] for r in rows]
    assert late[0] < 0.05 and late[3] > 0.4
    assert summary["late_max_s"] == max(late)
    lat = [r["done"] - r["due"] for r in rows]
    assert 0.2 <= lat[0] < 0.3
    assert lat[3] > 0.2 * 4 - 0.15 - 0.05       # queued behind three


def test_open_loop_with_enough_senders_is_on_time(tmp_path):
    reqs = [{"method": "GET", "path": "/x", "headers": {}}]
    phase = {"loop": "open", "threads": 8,
             "due": [[0, 0.02 * i] for i in range(8)]}
    rows, summary = run_client(tmp_path, phase, reqs)
    assert summary["late_max_s"] < 0.05
    assert all(0.2 <= r["done"] - r["due"] < 0.3 for r in rows)


def test_closed_loop_and_bursts(tmp_path):
    reqs = [{"method": "GET", "path": "/x", "headers": {}}]
    rows, _ = run_client(tmp_path, {
        "loop": "closed", "clients": 2, "seconds": 0.5,
        "order": [[0] * 10, [0] * 10]}, reqs)
    assert 4 <= len(rows) <= 8          # 2 clients x ~3 of 0.2 s in 0.5 s
    rows, _ = run_client(tmp_path, {"loop": "burst",
                                    "bursts": [[0, 0, 0], [0]]}, reqs)
    assert len(rows) == 4
    assert max(r["sent"] for r in rows[:3]) - min(
        r["sent"] for r in rows[:3]) < 0.05
