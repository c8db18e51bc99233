"""The comparison that decides ``correct``.

Once the window has closed and the engine is freed, a sample drawn from
the seed of the requests that completed inside the window, the one with
the longest context always among them, is run through the plain
reference: each prompt with its served tokens, teacher-forced, once.  At
every served position the reading is how far the reference's logit of
the served token lies below the reference's best.  The number compared
is the widest such gap over the sample.
"""

from __future__ import annotations

import numpy as np

from bench.loops.sizes import rng


def sample(records: dict, seconds: float, spec: dict, seed: int) -> list:
    """[(prompt, served tokens)]: the longest completed request, then
    others in an order drawn from the seed, ``spec["requests"]`` in all
    (or as many as completed)."""
    done = sorted(rid for rid, r in records.items()
                  if r.status == "completed" and r.done_at <= seconds
                  and r.tokens)
    if not done:
        return []
    longest = max(done, key=lambda i: len(records[i].prompt)
                  + len(records[i].tokens))
    rest = [i for i in done if i != longest]
    order = rng(seed, 7).permutation(len(rest))
    picked = [longest] + [rest[k] for k in order[:spec["requests"] - 1]]
    return [(records[i].prompt, records[i].tokens) for i in picked]


def compare(reference, params, items, max_context: int,
            control: bool = False) -> dict:
    """Widest gap over ``items``; with ``control`` also the widest gap
    of the tokens the fp8 control puts first at the same positions."""
    gaps, ctrl = [], []
    for prompt, served in items:
        seq = np.concatenate([np.asarray(prompt, np.int32),
                              np.asarray(served, np.int32)])
        P, n = len(prompt), len(seq)
        tokens = np.zeros(max_context, np.int32)
        tokens[:n] = seq
        target = np.full(max_context, -1, np.int32)
        target[P - 1:n - 1] = seq[P:]
        g, c = reference.gaps(params, tokens, target, control)
        mask = target >= 0
        gaps.append(g[mask])
        if control:
            ctrl.append(c[mask])
    allg = np.concatenate(gaps) if gaps else np.zeros(0)
    out = {"max_gap": float(allg.max()) if allg.size else float("nan"),
           "tokens": int(allg.size), "exact": int((allg == 0).sum())}
    if control:
        allc = np.concatenate(ctrl)
        out["control_max_gap"] = float(allc.max()) if allc.size else \
            float("nan")
    return out
