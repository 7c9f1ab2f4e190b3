"""The one traffic generator.  A mix is a JSON file under ``bench/traffic/``
whose parameters this module reads; no mix has code of its own.

A mix is a set of live *sessions*.  Turn 1 of a session is a shared prefix
(drawn by Zipf from a pool, or none) followed by a fresh user message;
each later turn is the previous prompt, a fresh answer (the engine does
not decode, so it stands for the answer) and a fresh user message.  After
``turns`` turns the session starts again.  A mix of unshared prompts is a
single session of one turn with no prefix pool.  Requests arrive on a
schedule at the cell's rate (``bench/rates/<cell>.json``), whether or not
earlier ones have been served: the loop is open.

Keys of a mix:

- ``prefix_pool``: ``{"count", "tokens", "zipf_s"}``, or null;
- ``sessions``, ``turns``;
- ``first_user_tokens``: an int, or a list that new sessions cycle through
  in a shuffled order, so every length is drawn equally often;
- ``answer_tokens``, ``user_tokens``: appended by each later turn;
- ``warm_sessions``: sessions start at turns spread evenly over
  ``1..turns``, and set-up serves each one's history;
- ``lead_in_s``: unmeasured traffic before the window;
- ``min_cached_share``: the least share of prompt tokens the hierarchy must
  reuse in the window (null where the mix shares nothing).

- ``schedule_seed``: draws the shape of the traffic (below).

Steadiness: every seed gets the same work.  The seed makes the content:
every token, so every prompt and system prompt (and, in the harness, the
weights).  The shape comes from the mix's ``schedule_seed``, the same for
every seed: the arrival times (the quantiles of an exponential
at the rate, in a shuffled order), the session each request continues
(each round of requests visits every live session once), the turn each
session starts at, and the Zipf rank of each new session's system
prompt.  A queue turns a change of arrival order into a change
of TTFT far larger than a run's own noise, so that order is not left to
the seed.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence

import numpy as np


@dataclass
class Prompt:
    rid: int
    tokens: List[int]
    shared: int  # longest block-aligned prefix held by an earlier prompt


@dataclass
class _Session:
    turn: int = 1
    history: Optional[List[int]] = None


def _cycle(rng: np.random.Generator, values: Sequence):
    """Endless draws that visit every value once per pass, in shuffled order."""
    while True:
        for i in rng.permutation(len(values)):
            yield values[int(i)]


class Traffic:
    """Prompts of one mix, made from ``seed`` for a model of ``vocab`` ids.
    ``scale`` divides every token count (the CPU rehearsal)."""

    def __init__(self, mix: Dict, vocab: int, block: int, seed: int, scale: int = 1):
        self.mix = mix
        self.block = block
        self.vocab = vocab
        self._tok = np.random.default_rng(np.random.SeedSequence(seed))
        shape = np.random.SeedSequence(int(mix["schedule_seed"])).spawn(4)
        self._prefix_rng, self._order, self._sched, self._first = (
            np.random.default_rng(s) for s in shape)

        def tokens(n: int) -> int:
            n //= scale
            if n % block:
                raise ValueError(f"{n} tokens is not a whole number of {block}-token blocks")
            return n

        pool = mix.get("prefix_pool") or {"count": 0, "tokens": 0, "zipf_s": 1.0}
        self.prefixes = [self._fresh(tokens(pool["tokens"])) for _ in range(pool["count"])]
        if self.prefixes:
            w = 1.0 / np.arange(1, len(self.prefixes) + 1) ** pool["zipf_s"]
            self._zipf = w / w.sum()
        first = mix["first_user_tokens"]
        self.first_user = [tokens(n) for n in (first if isinstance(first, list) else [first])]
        self._first_cycle = _cycle(self._first, self.first_user)
        self.answer = tokens(mix.get("answer_tokens", 0))
        self.user = tokens(mix.get("user_tokens", 0))
        self.turns = int(mix["turns"])
        self.lead_in_s = float(mix.get("lead_in_s", 0.0))
        self.min_cached_share = mix.get("min_cached_share")
        self.sessions = [_Session() for _ in range(int(mix["sessions"]))]
        self._round: List[int] = []
        self._rid = 0
        self._known: set = set()
        self._warm: List[Prompt] = []
        if mix.get("warm_sessions"):
            starts = [i % self.turns + 1 for i in range(len(self.sessions))]
            for s, t0 in zip(self.sessions, self._order.permutation(starts)):
                for _ in range(int(t0) - 1):
                    self._advance(s)
                if s.history is not None:
                    self._warm.append(self._prompt(s.history))

    # ------------------------------------------------------------ prompts
    def _fresh(self, n: int) -> List[int]:
        return self._tok.integers(0, self.vocab, n).tolist()

    def _advance(self, s: _Session) -> List[int]:
        if s.turn == 1:
            head = []
            if self.prefixes:
                head = self.prefixes[int(self._prefix_rng.choice(len(self.prefixes), p=self._zipf))]
            toks = list(head) + self._fresh(next(self._first_cycle))
        else:
            toks = s.history + self._fresh(self.answer) + self._fresh(self.user)
        s.history = toks
        s.turn += 1
        if s.turn > self.turns:
            s.turn, s.history = 1, None
        return toks

    def _prompt(self, toks: List[int]) -> Prompt:
        """Record ``toks`` and how much of it earlier prompts hold."""
        B = self.block
        h, shared, matching = 0, 0, True
        for i in range(len(toks) // B):
            h = hash((h, tuple(toks[i * B:(i + 1) * B])))
            if matching and h in self._known:
                shared = (i + 1) * B
            else:
                matching = False
                self._known.add(h)
        self._rid += 1
        return Prompt(self._rid, toks, shared)

    def warmup(self) -> List[Prompt]:
        """Histories of the sessions that start past turn 1."""
        return list(self._warm)

    def next(self) -> Prompt:
        if not self._round:
            self._round = self._order.permutation(len(self.sessions)).tolist()
        return self._prompt(self._advance(self.sessions[self._round.pop()]))

    def lengths(self) -> List[int]:
        """Every prompt length the mix makes."""
        head = len(self.prefixes[0]) if self.prefixes else 0
        step = self.answer + self.user
        return sorted({head + f + t * step for f in self.first_user for t in range(self.turns)})

    # ----------------------------------------------------------- arrivals
    def arrivals(self, rate: float, seconds: float) -> List[float]:
        """Due offsets in ``[0, seconds)``: ``round(rate *
        seconds)`` gaps at the exponential's quantiles, in the schedule's
        shuffled order."""
        n = int(round(rate * seconds))
        gaps = [-math.log(1.0 - (j + 0.5) / n) / rate for j in range(n)]
        gaps = [gaps[int(i)] for i in self._sched.permutation(n)]
        out, t = [], 0.0
        for g in gaps:
            out.append(t)
            t += g
        return out
