"""The one traffic generator: sessions of turns, read from a mix's data file.

A mix (``bench/traffic/<name>.json``) gives the turns per session, the
first prompt's lengths, the tokens each later turn appends, the tokens each
turn generates, the mean gap between a reply and the next turn, the offered
request rate and the store the cell runs with. Every later prompt of a
session is the one before it plus ``append_tokens`` new tokens, so a session
is one token array and turn k's prompt is its first ``prompt_lens[k]``
tokens. The tokens of the history (the turns' outputs included) are drawn
from the seed, never taken from the model.

Arrival times and gaps are one fixed draw from the mix's ``schedule_seed``:
every ``--seed`` gets the same arrivals and the same multiset of prompt
lengths. The seed draws the tokens and the order of the lengths within each
whole block of ``len(first_prompt_tokens)`` consecutive sessions (a last,
partial block keeps the file's order), so the offered
work is the same from seed to seed and only its order and content change.
Sessions start in ``[-history_s, horizon_s)``: those started before the
window opens are in mid-conversation then, and set-up serves their earlier
turns (``Session.history_turns``).
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path
from typing import List, Tuple

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"


def load_mix(name: str) -> dict:
    with open(TRAFFIC_DIR / f"{name}.json") as f:
        mix = json.load(f)
    mix["name"] = name
    return mix


@dataclass
class Session:
    sid: str
    start: float                 # seconds from window open (negative: before)
    prompt_lens: List[int]       # prompt length of each turn
    gaps: List[float]            # gap after turn k's reply before turn k+1
    tokens: np.ndarray           # the last turn's prompt; turn k uses a prefix
    output_tokens: int
    nominal_turn_s: float

    def prompt(self, k: int) -> List[int]:
        return self.tokens[:self.prompt_lens[k]].tolist()

    def nominal_due(self, k: int) -> float:
        """When turn k would be due if every earlier turn took the mix's
        nominal service time: places the history before the window."""
        return self.start + sum(self.nominal_turn_s + g
                                for g in self.gaps[:k])

    @property
    def history_turns(self) -> int:
        """Turns due before the window opens (served in set-up)."""
        return sum(self.nominal_due(k) < 0
                   for k in range(len(self.prompt_lens)))


def prompt_lengths(mix: dict, first: int) -> List[int]:
    return [first + k * mix["append_tokens"] for k in range(mix["turns"])]


def warm_shapes(mix: dict) -> List[Tuple[int, int]]:
    """Every (new tokens, cached prefix) prefill shape the mix can produce:
    each turn's whole prompt as a miss (a first turn, or a later one whose
    prefix was evicted), and each later turn's suffix after the turn before
    it, for a hit. A single-turn mix has misses only."""
    shapes = set()
    for first in mix["first_prompt_tokens"]:
        lens = prompt_lengths(mix, first)
        shapes.update((n, 0) for n in lens)
        shapes.update((mix["append_tokens"], p) for p in lens[:-1])
    return sorted(shapes)


def build(mix: dict, seed: int, vocab: int) -> List[Session]:
    """The sessions of ``mix`` for ``seed``, ordered by start time."""
    fixed = np.random.default_rng(mix["schedule_seed"])
    rng = np.random.default_rng([seed, 0x5E55])
    turns = mix["turns"]
    session_rate = mix["rate_req_per_s"] / turns
    lo, hi = -float(mix["history_s"]), float(mix["horizon_s"])
    starts, t = [], lo
    while True:
        t += fixed.exponential(1.0 / session_rate)
        if t >= hi:
            break
        starts.append(t)
    gaps = fixed.exponential(mix["gap_mean_s"], size=(len(starts), turns))
    firsts = np.asarray(mix["first_prompt_tokens"])
    blocks, rest = divmod(len(starts), len(firsts))
    order = np.concatenate([rng.permutation(firsts) for _ in range(blocks)]
                           + [firsts[:rest]])
    sessions = []
    for i, start in enumerate(starts):
        lens = prompt_lengths(mix, int(order[i]))
        toks = rng.integers(0, vocab, size=lens[-1], dtype=np.int64)
        sessions.append(Session(
            sid=f"s{i}", start=float(start), prompt_lens=lens,
            gaps=[float(g) for g in gaps[i]], tokens=toks,
            output_tokens=int(mix["output_tokens"]),
            nominal_turn_s=float(mix["nominal_turn_s"])))
    return sessions
