"""Open loop: independent users.  Each request is due at its own time
whatever the engine is doing, and its TTFT counts from when it was due.

Traffic keys: ``rate`` (requests/s), ``arrival`` (``{"dist": "poisson"}``
or ``{"dist": "gamma", "cv": c}``), ``prompt`` and ``output`` length
specs (see ``sizes.quantiles``).  A window of ``seconds`` offers
``round(rate * seconds)`` requests, all due inside it.
"""

from __future__ import annotations

import numpy as np

from bench.loops import sizes


def plan(traffic: dict, seed: int, seconds: float, vocab: int,
         rate: "float | None" = None) -> list[dict]:
    """The requests of one window, in due order: ``{"id", "prompt",
    "max_new", "due"}``.  ``rate`` overrides the traffic's own (the knee
    sweep); the lengths and the order of gaps stay those of ``seed``."""
    rate = traffic["rate"] if rate is None else rate
    n = max(1, round(rate * seconds))
    order = sizes.rng(seed, 1)
    p_set, o_set = sizes.requests(traffic, n)
    idx = order.permutation(n)
    p_len, o_len = p_set[idx], o_set[idx]
    gap = order.permutation(sizes.gaps(traffic["arrival"], n, rate))
    due = np.concatenate([[0.0], np.cumsum(gap)[:-1]])
    toks = sizes.rng(seed, 2)
    return [{"id": i, "prompt": sizes.prompt(toks, p_len[i], vocab),
             "max_new": int(o_len[i]), "due": float(due[i])}
            for i in range(n)]


def drive(client, traffic: dict, reqs: list[dict], seconds: float) -> None:
    """Submit each request once the clock passes its due time, step the
    engine while it has work, and idle until the next due time when it
    has none.  Stops at the end of the window."""
    i = 0
    while True:
        now = client.now()
        if now >= seconds:
            return
        while i < len(reqs) and reqs[i]["due"] <= now:
            client.submit(reqs[i], sent=reqs[i]["due"])
            i += 1
        if client.has_work():
            client.step()
        else:
            nxt = reqs[i]["due"] if i < len(reqs) else seconds
            client.idle(min(nxt, seconds))
