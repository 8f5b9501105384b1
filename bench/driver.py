"""Drives a schedule through ``RealExecutionEngine.generate`` in real time.

One server, one request at a time, first come first served by due time.
Session starts are open loop: each is due at its scheduled time whatever the
server is doing. Within a session, turn k+1 is due its gap after turn k's
reply came back, since its prompt holds that reply. Every request is timed
from when it was due:

* TTFT: from due until the first token is on the host (the engine's first
  decode call, which follows the host read of the prefill's token);
* TPOT: from the first token until ``generate`` returns, over the decode
  steps in between (``num_new`` of them).

Requests due in ``[0, seconds)`` are attempted. After the window closes no
new turn is issued, and those already due are served to the end (the
latency counts the wait); one still unserved ``drain_s`` after the close
has failed.
"""
from __future__ import annotations

import contextlib
import heapq
import math
import time
from dataclasses import dataclass, field
from typing import Callable, List, Optional

from bench.schedule import Session


@dataclass
class Request:
    sid: str
    turn: int
    due: float
    prompt_len: int
    num_new: int
    t_call: float = math.nan
    t_first: float = math.nan
    t_end: float = math.nan
    reused: int = 0
    computed: int = 0
    prefill_time_s: float = math.nan
    decode_time_s: float = math.nan
    tokens: List[int] = field(default_factory=list)
    failed: bool = False

    @property
    def ttft_s(self) -> float:
        return math.inf if self.failed else self.t_first - self.due

    @property
    def tpot_s(self) -> float:
        return math.inf if self.failed else \
            (self.t_end - self.t_first) / self.num_new


class FirstTokenClock:
    """Wraps an engine's decode program to stamp the host time of its first
    call in each ``generate``: by then the first token has been read back."""

    def __init__(self, engine, clock: Callable[[], float]):
        self.inner = engine._decode
        self.clock = clock
        self.stamp: Optional[float] = None
        engine._decode = self

    def __call__(self, *args):
        if self.stamp is None:
            self.stamp = self.clock()
        return self.inner(*args)


def serve_history(engine, sessions: List[Session]) -> int:
    """Serve, in set-up, every turn due before the window opens, in the
    order they would have come, so the store holds what a steady state
    would. Only the prefill matters to the store, so these turns generate
    no tokens. Returns the number of turns served."""
    due = sorted((s.nominal_due(k), i, k) for i, s in enumerate(sessions)
                 for k in range(s.history_turns))
    for t, i, k in due:
        s = sessions[i]
        engine.generate(s.sid, s.prompt(k), num_new=0, now=t)
    return len(due)


def drive(engine, sessions: List[Session], seconds: float, *,
          drain_s: float = 120.0,
          span: Callable = lambda name: contextlib.nullcontext(),
          clock: Callable[[], float] = time.perf_counter,
          sleep: Callable[[float], None] = time.sleep) -> tuple:
    """Serve every turn due in ``[0, seconds)``. Returns the requests in
    the order served, the window's start on ``clock``, and the host's
    lateness in starting requests that found the server idle (seconds:
    the largest and the sum)."""
    first = FirstTokenClock(engine, clock)
    heap, seq = [], 0
    for i, s in enumerate(sessions):
        k = s.history_turns
        if k < len(s.prompt_lens):
            d = s.nominal_due(k)
            if d < seconds:
                heapq.heappush(heap, (d, seq, i, k))
                seq += 1
    served: List[Request] = []
    late_max = late_sum = 0.0
    t0 = clock()
    now = lambda: clock() - t0
    while heap:
        due, _, i, k = heapq.heappop(heap)
        s = sessions[i]
        r = Request(sid=s.sid, turn=k, due=due, prompt_len=s.prompt_lens[k],
                    num_new=s.output_tokens)
        if now() > seconds + drain_s:
            r.failed = True
            served.append(r)
            continue
        wait = due - now()
        if wait > 0:
            with span("arrival_wait"):
                sleep(wait)
            late = max(now() - due, 0.0)
            late_max, late_sum = max(late_max, late), late_sum + late
        first.stamp = None
        r.t_call = now()
        with span("generate"):
            g = engine.generate(s.sid, s.prompt(k), num_new=r.num_new,
                                now=r.t_call)
        r.t_end = now()
        r.t_first = first.stamp - t0 if first.stamp is not None else r.t_end
        r.reused, r.computed = g.reused_tokens, g.prefill_tokens_computed
        r.prefill_time_s, r.decode_time_s = g.prefill_time_s, g.decode_time_s
        r.tokens = list(g.tokens)
        served.append(r)
        if k + 1 < len(s.prompt_lens):
            nxt = r.t_end + s.gaps[k]
            if nxt < seconds:
                heapq.heappush(heap, (nxt, seq, i, k + 1))
                seq += 1
    engine._decode = first.inner
    return served, t0, (late_max, late_sum)
