"""Surface ``wire``: the cell's clients speak the Presto wire protocol to
``run_server(context=ctx, blocking=False)`` started in the run's process.

The clients live in ``loadgen.py``, a child process that never imports JAX.
While the window runs this process only reads the child's lines and keeps
each request's server-side trace (``ctx.traces`` holds the last 256 only).
"""
from __future__ import annotations

import json
import os
import queue
import subprocess
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
CLOSE_GRACE_S = 120.0


class Surface:
    def __init__(self, ctx, workload: dict, queries: dict, seed: int,
                 seconds: float, emit):
        self.ctx, self.workload, self.queries = ctx, workload, queries
        self.seed, self.seconds, self.emit = seed, seconds, emit
        self.server = self.child = None

    def start(self) -> None:
        from dask_sql_tpu.server.app import run_server

        t0 = time.perf_counter()
        self.server = run_server(context=self.ctx, host="127.0.0.1", port=0,
                                 blocking=False)
        warm = self.ctx.warmup
        if warm is not None:
            # server boot replays the profiled queries in the background;
            # wait for it as a /v1/health client would
            warm.join(600.0)
            if not warm.ready:
                raise RuntimeError(f"boot warm-up not ready: {warm.status()}")
        self.emit(phase="server_boot", seconds=time.perf_counter() - t0,
                  port=self.server.port,
                  warmup=None if warm is None else warm.status())

        # the child is up, has sent one request of each query over the
        # wire (its first connection, the server's first page) and waits
        t0 = time.perf_counter()
        self.child = subprocess.Popen(
            [sys.executable, os.path.join(os.path.dirname(HERE),
                                          "loadgen.py")],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
            bufsize=1)
        plan = {"workload": self.workload, "queries": self.queries,
                "seed": self.seed, "port": self.server.port,
                "seconds": self.seconds}
        self.child.stdin.write(json.dumps(plan) + "\n")
        self.child.stdin.flush()
        line = self.child.stdout.readline().strip()
        if line != "ready":
            raise RuntimeError(f"load generator said {line!r}, not 'ready'")
        self.emit(phase="loadgen_ready", seconds=time.perf_counter() - t0)

    def run(self, tick):
        lines: "queue.Queue" = queue.Queue()

        def pump():
            # this thread fetches each request's trace as its line arrives:
            # ``ctx.traces`` keeps the last 256, and the main thread may be
            # held up for seconds starting or stopping the profiler
            for line in self.child.stdout:
                rec = json.loads(line)
                if "qid" in rec:
                    rec["trace"] = self.ctx.traces.get(rec["qid"])
                lines.put(rec)
            lines.put(None)

        reader = threading.Thread(target=pump, daemon=True)
        reader.start()
        self.child.stdin.write("go\n")
        self.child.stdin.flush()
        records, start, end, done = [], None, None, False
        give_up = time.perf_counter() + self.seconds + CLOSE_GRACE_S
        while not done and time.perf_counter() < give_up:
            tick(time.perf_counter())
            try:
                rec = lines.get(timeout=0.02)
            except queue.Empty:
                continue
            if rec is None:
                break
            if "window_start" in rec:
                start, end = rec["window_start"], rec["window_end"]
            elif "finished" in rec:
                done = True
            else:
                records.append(rec)
        if not done:
            self.child.kill()
            raise RuntimeError("the load generator did not finish")
        reader.join(timeout=10)
        return records, start, end

    def stop(self) -> None:
        if self.child is not None:
            for pipe in (self.child.stdin, self.child.stdout):
                pipe.close()
            try:
                self.child.wait(timeout=10)
            except subprocess.TimeoutExpired:
                self.child.kill()
                self.child.wait()
        if self.server is not None:
            self.server.shutdown()
