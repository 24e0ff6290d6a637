"""Closed-loop HTTP clients, run as a child process that never imports JAX.

    python3 clients.py <spec.json>

The spec names the server's port, the request path, the number of clients,
the warm-up bodies, the window's bodies and its length in seconds. Each
client thread sends its next request only when the answer to its last one
has arrived. The warm-up runs to its end first; then the process prints
``WINDOW <monotonic start>`` and starts the window. No request is sent once
the window has closed, and every request sent in it is waited for. The
records of the window's requests go to the spec's ``out`` file as JSON, and
``DONE`` is printed last.

``time.monotonic`` is one clock for every process of the machine, so the
parent reads the window's start on its own clock.
"""
from __future__ import annotations

import http.client
import json
import sys
import threading
import time

REPLY_TIMEOUT_S = 120.0


class Client:
    """One keep-alive connection; reconnects after a transport error."""

    def __init__(self, port: int, path: str):
        self.port = port
        self.path = path
        self.conn = None

    def send(self, body: dict) -> dict:
        """One request; returns its record (times on the monotonic clock)."""
        data = json.dumps(body)
        sent = time.monotonic()
        try:
            if self.conn is None:
                self.conn = http.client.HTTPConnection(
                    "127.0.0.1", self.port, timeout=REPLY_TIMEOUT_S)
            self.conn.request("POST", self.path, data,
                              {"Content-Type": "application/json"})
            resp = self.conn.getresponse()
            payload = resp.read()
            done = time.monotonic()
            answer = json.loads(payload)
            return {"sent": sent, "done": done, "status": resp.status,
                    "answer": answer}
        except (OSError, http.client.HTTPException, ValueError) as e:
            if self.conn is not None:
                self.conn.close()
            self.conn = None
            return {"sent": sent, "done": None, "status": None,
                    "error": f"{type(e).__name__}: {e}"}

    def close(self):
        if self.conn is not None:
            self.conn.close()


def closed_loop(port: int, path: str, bodies: list, clients: int,
                until: float = float("inf")) -> list:
    """Send ``bodies`` in order from ``clients`` closed-loop threads until
    they run out or the monotonic clock passes ``until``; returns one
    record per request sent, with its index into ``bodies``."""
    lock = threading.Lock()
    next_index = [0]
    records = []

    def worker():
        client = Client(port, path)
        try:
            while time.monotonic() < until:
                with lock:
                    i = next_index[0]
                    if i >= len(bodies):
                        return
                    next_index[0] += 1
                rec = client.send(bodies[i])
                rec["index"] = i
                with lock:
                    records.append(rec)
        finally:
            client.close()

    threads = [threading.Thread(target=worker, daemon=True)
               for _ in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    records.sort(key=lambda r: r["index"])
    return records


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    with open(argv[0]) as f:
        spec = json.load(f)
    warm = closed_loop(spec["port"], spec["path"], spec["warmup"],
                       spec["clients"])
    bad = [r for r in warm if r["status"] != 200]
    start = time.monotonic()
    print(f"WINDOW {start!r} warmup {len(warm)} failed {len(bad)}",
          flush=True)
    records = closed_loop(spec["port"], spec["path"], spec["window"],
                          spec["clients"], until=start + spec["seconds"])
    exhausted = len(records) >= len(spec["window"])
    with open(spec["out"], "w") as f:
        json.dump({"start": start, "seconds": spec["seconds"],
                   "exhausted": exhausted, "records": records}, f)
    print("DONE", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
