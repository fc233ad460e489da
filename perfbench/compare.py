"""The comparison that decides ``correct``: every answer the timed requests
returned, against the plain reference's answer for the same parameters.

Numbers compared, each with a limit of its own (``run.py`` prints them):

* ``rel_err.<query>``  the widest relative gap of any float cell of any answer
  of that query from the reference's float64 value; the limit is the query
  file's ``limits.rel_err`` (set from chip readings, PERF.md section 2);
* ``answers_wrong``    answers whose shape, column names, group keys or exact
  columns (counts) differ, or that hold a non-finite number; limit 0;
* ``requests_failed``  requests that raised, were refused, never came back,
  or compiled inside the window; limit 0;
* ``ladder_step_downs``  ``resilience.degraded`` + ``resilience.rung.cpu``;
  limit 0;
* ``not_on_compiled_rung``  answered requests whose trace names no
  ``rung:compiled_*`` / ``rung:spmd_*`` span; limit 0.
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional

COMPILED_RUNG_PREFIXES = ("rung:compiled_", "rung:spmd_")


def answer_gap(query: dict, got: dict, ref: dict) -> Optional[float]:
    """Widest relative gap of ``got`` from ``ref`` over the float cells, or
    None where the answer is wrong in a way no tolerance covers."""
    if list(got["columns"]) != list(ref["columns"]) \
            or len(got["rows"]) != len(ref["rows"]):
        return None
    worst = 0.0
    for grow, rrow in zip(got["rows"], ref["rows"]):
        if len(grow) != len(rrow):
            return None
        for name, g, r in zip(ref["columns"], grow, rrow):
            if name in query["key_columns"]:
                if str(g) != str(r):
                    return None
            elif name in query["exact_columns"]:
                if g is None or int(g) != int(r) or float(g) != float(int(g)):
                    return None
            else:
                if g is None or not math.isfinite(float(g)):
                    return None
                worst = max(worst, abs(float(g) - float(r))
                            / max(abs(float(r)), 1e-300))
    return worst


def compare_window(records: List[dict], queries: Dict[str, dict],
                   references: dict, ladder: Dict[str, int]) -> dict:
    """``records``: one per request sent in the window, with ``query``,
    ``params``, ``answer`` ({columns, rows} or None), ``error``, ``spans``
    (names of the request's trace, or None where the trace is gone).  Each
    record gets ``ok`` (answered and right) and, where compared, ``gap``:
    ``run.py`` counts only ``ok`` requests into ``queries_per_s``."""
    numbers = {f"rel_err.{name}": 0.0 for name in queries}
    wrong = failed = off_rung = 0
    for rec in records:
        spans = rec.get("spans")
        compiled_in_window = bool(spans) and any(
            s.startswith("compile:") for s in spans)
        if rec.get("error") or rec.get("answer") is None \
                or compiled_in_window:
            failed += 1
            rec["ok"] = False
            continue
        if spans is not None and not any(
                s.startswith(COMPILED_RUNG_PREFIXES) for s in spans):
            off_rung += 1
        gap = answer_gap(queries[rec["query"]], rec["answer"],
                         references[rec["query"]].answer(rec["params"]))
        rec["ok"] = gap is not None
        if gap is None:
            wrong += 1
        else:
            rec["gap"] = gap
            key = f"rel_err.{rec['query']}"
            numbers[key] = max(numbers[key], gap)
    limits = {f"rel_err.{name}": float(q["limits"]["rel_err"])
              for name, q in queries.items()}
    numbers.update(answers_wrong=wrong, requests_failed=failed,
                   ladder_step_downs=int(sum(ladder.values())),
                   not_on_compiled_rung=off_rung)
    compared = {name: {"value": value, "limit": limits.get(name, 0)}
                for name, value in numbers.items()}
    return {"compared": compared,
            "within": all(c["value"] <= c["limit"]
                          for c in compared.values()),
            "answers_compared": len(records) - failed}
