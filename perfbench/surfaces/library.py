"""Surface ``library``: the cell's clients call ``Context.sql(q).compute()``.

Each client is a thread of the run's own process (the library has no other
way to be called); a request is answered when ``compute()`` has returned the
pandas frame.
"""
from __future__ import annotations

import threading
import time

from perfbench import traffic


def frame_answer(frame) -> dict:
    return {"columns": [str(c) for c in frame.columns],
            "rows": [[v.item() if hasattr(v, "item") else v for v in row]
                     for row in frame.itertuples(index=False)]}


class Surface:
    def __init__(self, ctx, workload: dict, queries: dict, seed: int,
                 seconds: float, emit):
        self.ctx, self.workload, self.queries = ctx, workload, queries
        self.seed, self.seconds, self.emit = seed, seconds, emit

    def start(self) -> None:
        pass

    def _client(self, number: int, end: float, records: list) -> None:
        for req in traffic.stream(self.workload, self.queries, self.seed,
                                  number):
            sent = time.perf_counter()
            if sent >= end:
                return
            rec = req.record(sent)
            try:
                rec["frame"] = self.ctx.sql(req.sql).compute()
                rec["done"] = time.perf_counter()
                rec["trace"] = self.ctx.last_trace
            except Exception as exc:  # a failed request, never a crash
                rec["done"] = time.perf_counter()
                rec["error"] = f"{type(exc).__name__}: {exc}"[:300]
            records.append(rec)

    def run(self, tick):
        start = time.perf_counter()
        end = start + self.seconds
        per_client = [[] for _ in range(int(self.workload["clients"]))]
        threads = [threading.Thread(target=self._client,
                                    args=(n, end, per_client[n]))
                   for n in range(len(per_client))]
        for t in threads:
            t.start()
        while any(t.is_alive() for t in threads):
            tick(time.perf_counter())
            time.sleep(0.02)
        for t in threads:
            t.join()
        records = [r for recs in per_client for r in recs]
        for rec in records:  # rows for the comparison, after the clock
            if "frame" in rec:
                rec["answer"] = frame_answer(rec.pop("frame"))
        return records, start, end

    def stop(self) -> None:
        pass
