"""The wire cells' load generator: a child process that never imports JAX.

It speaks the Presto wire protocol as a client does (``POST /v1/statement``,
then ``GET`` the ``nextUri`` until the reply carries none), with the cell's
clients as threads.  The parent holds the chip and the server; this process
holds only sockets, so the generator does not share the server's interpreter
lock.

stdin, one JSON line: {"workload", "queries", "seed", "port", "seconds"},
then a line ``go`` when the window opens.  stdout: ``ready``, then one JSON
line per request as it completes, then {"finished": t}.  All times are
``time.perf_counter()``, which on Linux is one clock for every process.
"""
from __future__ import annotations

import http.client
import json
import os
import sys
import threading
import time
from urllib.parse import urlsplit

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from perfbench import traffic  # noqa: E402  (standard library only)

REPLY_TIMEOUT_S = 90.0  # the window, and a minute past its close


def wire_query(conn: http.client.HTTPConnection, sql: str, backoff_ms):
    """One statement to its last page: (query id, columns, rows, polls)."""
    def call(method, path, body=None):
        conn.request(method, path, body=body)
        resp = conn.getresponse()
        payload = json.loads(resp.read())
        if resp.will_close:
            conn.close()
        return payload

    payload = call("POST", "/v1/statement", sql.encode())
    polls, wait_ms = 0, 0.0
    deadline = time.perf_counter() + REPLY_TIMEOUT_S
    while "nextUri" in payload:
        if time.perf_counter() > deadline:
            raise TimeoutError("no final page within the reply timeout")
        if wait_ms:
            time.sleep(wait_ms / 1000.0)
        wait_ms = min(max(wait_ms * 2, backoff_ms[0]), backoff_ms[1])
        payload = call("GET", urlsplit(payload["nextUri"]).path)
        polls += 1
    if "error" in payload:
        raise RuntimeError(str(payload["error"])[:300])
    return (payload["id"], [c["name"] for c in payload.get("columns", [])],
            payload.get("data", []), polls)


def main() -> int:
    plan = json.loads(sys.stdin.readline())
    workload, queries = plan["workload"], plan["queries"]
    backoff_ms = workload.get("poll_backoff_ms", [1, 8])
    out_lock = threading.Lock()

    def emit(obj):
        with out_lock:
            sys.stdout.write(json.dumps(obj) + "\n")
            sys.stdout.flush()

    def client(number: int, end: float):
        conn = http.client.HTTPConnection("127.0.0.1", plan["port"],
                                          timeout=REPLY_TIMEOUT_S)
        for req in traffic.stream(workload, queries, plan["seed"], number):
            sent = time.perf_counter()
            if sent >= end:
                break
            rec = req.record(sent)
            try:
                qid, columns, rows, polls = wire_query(conn, req.sql,
                                                       backoff_ms)
                rec.update(qid=qid, polls=polls,
                           answer={"columns": columns, "rows": rows})
            except Exception as exc:  # reported as a failed request
                rec.update(error=f"{type(exc).__name__}: {exc}"[:300])
                conn.close()
            rec["done"] = time.perf_counter()
            emit(rec)
        conn.close()

    # one request of each query before the window: this process's first
    # connection and the server's first page are set-up, not traffic
    warm = http.client.HTTPConnection("127.0.0.1", plan["port"],
                                      timeout=REPLY_TIMEOUT_S)
    warm_rng = traffic.warm_rng(plan["seed"])
    for query in queries.values():
        wire_query(warm, traffic.render(
            query, traffic.draw_params(query, warm_rng)), backoff_ms)
    warm.close()
    sys.stdout.write("ready\n")
    sys.stdout.flush()
    if sys.stdin.readline().strip() != "go":
        return 2
    start = time.perf_counter()
    end = start + float(plan["seconds"])
    emit({"window_start": start, "window_end": end})
    threads = [threading.Thread(target=client, args=(n, end))
               for n in range(int(workload["clients"]))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    emit({"finished": time.perf_counter()})
    return 0


if __name__ == "__main__":
    sys.exit(main())
