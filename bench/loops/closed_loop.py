"""Closed loop: ``clients`` callers, each of which sends its next request
the moment its previous one completes.  TTFT counts from the send.

Traffic keys: ``clients``, ``prompt`` and ``output`` length specs,
``deck`` (default ``clients``) and ``repeat`` (default false).  The
requests come in rounds of ``deck``, each the same set of (prompt,
output) lengths (see ``sizes``) in an order drawn from the seed and the
round, and are dealt in that order: the first ``clients`` at the start,
then the next one to whichever client's request completes.  A deck
larger than the clients reaches further into the tails of the length
distributions.

With ``repeat`` (and a deck of ``clients``), a client whose request
completes sends one of the same lengths with new prompt tokens, so the
batch always holds the first round's lengths.  Where a window completes
only some of the first round, dealing the next round in the seed's order
would make the seed choose which lengths enter the window; repeating
keeps the work the same for every seed.
"""

from __future__ import annotations

from bench.loops import sizes


class Deck:
    """The requests of one seed, in the order they are dealt; rounds are
    made on first use."""

    def __init__(self, traffic: dict, seed: int, vocab: int):
        self.C = traffic["clients"]
        self.R = traffic.get("deck", self.C)
        self.repeat = traffic.get("repeat", False)
        if self.repeat and self.R != self.C:
            raise ValueError("repeat needs a deck of as many as the clients")
        self.seed, self.vocab = seed, vocab
        self.p_set, self.o_set = sizes.requests(traffic, self.R)
        self.rounds: list[list[dict]] = []
        self.dealt = 0
        self.lengths: dict[int, tuple[int, int]] = {}

    def round(self, r: int) -> list[dict]:
        while len(self.rounds) <= r:
            k = len(self.rounds)
            order = sizes.rng(self.seed, 1, k)
            toks = sizes.rng(self.seed, 2, k)
            idx = order.permutation(self.R)
            p_len, o_len = self.p_set[idx], self.o_set[idx]
            self.rounds.append([
                {"id": k * self.R + i, "round": k,
                 "prompt": sizes.prompt(toks, p_len[i], self.vocab),
                 "max_new": int(o_len[i])} for i in range(self.R)])
        return self.rounds[r]

    def take(self, done: "int | None" = None) -> dict:
        """The next request; with ``repeat``, the one that replaces the
        completed request ``done``."""
        n = self.dealt
        self.dealt += 1
        if self.repeat and done is not None:
            p_len, max_new = self.lengths[done]
            req = {"id": n, "round": n // self.R, "max_new": max_new,
                   "prompt": sizes.prompt(sizes.rng(self.seed, 3, n),
                                          p_len, self.vocab)}
        else:
            r, i = divmod(n, self.R)
            req = self.round(r)[i]
        self.lengths[req["id"]] = (len(req["prompt"]), req["max_new"])
        return req


def plan(traffic: dict, seed: int, seconds: float, vocab: int) -> Deck:
    return Deck(traffic, seed, vocab)


def drive(client, traffic: dict, deck: Deck, seconds: float) -> None:
    for _ in range(deck.C):
        client.submit(deck.take(), sent=client.now())
    while client.now() < seconds and client.has_work():
        for rid in client.step():
            client.submit(deck.take(rid), sent=client.now())
