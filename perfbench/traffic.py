"""The one traffic generator: a cell's file of parameters -> request streams.

Imports nothing but the standard library, so the load generator's child
process (``loadgen.py``) can use it without ever importing JAX.

A cell file (``workloads/<cell>.json``) gives ``clients`` (closed loops: each
client sends its next request when the last one is answered), a ``mix`` of
queries with whole-number weights, and ``block``.  Every client draws its
requests from the seed in blocks of ``block * sum(weights)``: each block
holds every query exactly ``block * weight`` times, in an order the seed
shuffles.  So every seed sends the same set of work in another order, and the
window's share of each query does not swing with the seed.  Each request's
substitution parameters are drawn afresh, uniformly from the domains in the
query's file (TPC-H clauses 2.4.x.3).
"""
from __future__ import annotations

import datetime
import json
import os
import random
from dataclasses import dataclass
from typing import Dict, Iterator, List

ROOT = os.path.dirname(os.path.abspath(__file__))


def load(kind: str, name: str) -> dict:
    """``perfbench/<kind>/<name>.json``; the name is the file's own."""
    with open(os.path.join(ROOT, kind, name + ".json")) as f:
        return json.load(f)


@dataclass
class Request:
    client: int
    index: int
    query: str
    params: Dict[str, int]
    sql: str

    def record(self, sent: float) -> dict:
        """What a surface notes of this request as it sends it."""
        return {"client": self.client, "index": self.index,
                "query": self.query, "params": self.params, "sent": sent}


def draw_params(query: dict, rng: random.Random) -> Dict[str, int]:
    return {name: rng.randint(int(dom["low"]), int(dom["high"]))
            for name, dom in sorted(query["parameters"].items())}


def all_params(query: dict) -> Iterator[Dict[str, int]]:
    """Every combination of the query's substitution parameters."""
    names = sorted(query["parameters"])
    combos: List[Dict[str, int]] = [{}]
    for name in names:
        dom = query["parameters"][name]
        combos = [{**c, name: v} for c in combos
                  for v in range(int(dom["low"]), int(dom["high"]) + 1)]
    return iter(combos)


def render(query: dict, params: Dict[str, int]) -> str:
    """The query's text with its placeholders filled from ``params``."""
    filled = {}
    for placeholder, rule in query["placeholders"].items():
        value = params[rule["from"]] + rule.get("add", 0)
        if "days_before" in rule:  # a date literal, worked out by the client
            value = (datetime.date.fromisoformat(rule["days_before"])
                     - datetime.timedelta(days=value)).isoformat()
        elif "divide" in rule:
            value = value / rule["divide"]
        filled[placeholder] = rule["format"] % value
    return query["sql"].format(**filled)


def client_rng(seed: int, client: int) -> random.Random:
    return random.Random(f"perfbench:{int(seed)}:{int(client)}")


def warm_rng(seed: int) -> random.Random:
    """Parameters of the requests set-up sends; never the window's own."""
    return random.Random(f"perfbench:warm:{int(seed)}")


def stream(workload: dict, queries: Dict[str, dict], seed: int,
           client: int) -> Iterator[Request]:
    """The endless request stream of one closed-loop client, from the seed."""
    rng = client_rng(seed, client)
    block = [m["query"] for m in workload["mix"]
             for _ in range(int(m["weight"]) * int(workload.get("block", 1)))]
    index = 0
    while True:
        order = block[:]
        rng.shuffle(order)
        for name in order:
            params = draw_params(queries[name], rng)
            yield Request(client, index, name, params,
                          render(queries[name], params))
            index += 1


def queries_of(workload: dict) -> Dict[str, dict]:
    return {m["query"]: load("queries", m["query"]) for m in workload["mix"]}
