"""The traffic generator and the driver, run on a virtual clock: the same
seed gives the same schedule, every later prompt extends the one before it,
every seed offers the same work, and every prefill shape a run meets is
among the shapes warmed."""
from types import SimpleNamespace

import numpy as np
import pytest

from bench import cells, configs, driver, schedule
from repro.core.kvstore import KVStore
from repro.core.policies import POLICIES

MIXES = sorted({c["traffic"] for c in cells.load_benchmark()["workloads"]})
BIG_SEED = 2**31 + 2**30 + 12345


class FakeEngine:
    """``generate`` with the engine's store traffic and a modelled service
    time, on a virtual clock; records each prefill shape it runs."""

    def __init__(self, store, clock):
        self.store, self.clock, self.shapes = store, clock, []
        self._decode = lambda *a: None

    def generate(self, key, prompt, num_new=8, now=None):
        e = self.store.lookup(key, len(prompt), now)
        plen = e.num_tokens if e is not None else 0
        self.shapes.append((len(prompt) - plen, plen))
        self.clock[0] += 0.03 + 2e-5 * (len(prompt) - plen)
        self.store.insert(key, len(prompt), now, payload=(len(prompt), None))
        for _ in range(num_new):
            self._decode()
            self.clock[0] += 0.012
        return SimpleNamespace(tokens=[1] * num_new, reused_tokens=plen,
                               prefill_tokens_computed=len(prompt) - plen,
                               prefill_time_s=0.0, decode_time_s=0.0)


def _simulate(mix, seed, seconds, capacity_tokens=None):
    conf = configs.load(next(c["config"] for c in
                             cells.load_benchmark()["workloads"]
                             if c["traffic"] == mix["name"]))
    kvb = conf["dims"]["layers"] * conf["dims"]["kv_heads"] * \
        conf["dims"]["head_dim"] * 4
    cap = capacity_tokens or mix["store"]["capacity_tokens"]
    clock = [0.0]
    eng = FakeEngine(KVStore(cap * kvb, POLICIES[mix["store"]["policy"]],
                             kvb), clock)
    sessions = schedule.build(mix, seed, conf["dims"]["vocab"])
    driver.serve_history(eng, sessions)
    clock[0] = 0.0
    reqs, _, _ = driver.drive(
        eng, sessions, seconds, clock=lambda: clock[0],
        sleep=lambda s: clock.__setitem__(0, clock[0] + s))
    return eng, sessions, reqs


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_schedule(name):
    mix = schedule.load_mix(name)
    a = schedule.build(mix, BIG_SEED, 1000)
    b = schedule.build(mix, BIG_SEED, 1000)
    assert [(s.start, s.prompt_lens, s.gaps) for s in a] == \
        [(s.start, s.prompt_lens, s.gaps) for s in b]
    assert all(np.array_equal(x.tokens, y.tokens) for x, y in zip(a, b))


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_offers_the_same_work_in_another_order(name):
    mix = schedule.load_mix(name)
    a = schedule.build(mix, 1, 1000)
    b = schedule.build(mix, BIG_SEED, 1000)
    assert [s.start for s in a] == [s.start for s in b]
    assert [s.gaps for s in a] == [s.gaps for s in b]
    assert sorted(s.prompt_lens[0] for s in a) == \
        sorted(s.prompt_lens[0] for s in b)
    assert any(x.prompt_lens != y.prompt_lens for x, y in zip(a, b)) or \
        len(set(mix["first_prompt_tokens"])) == 1
    assert not all(np.array_equal(x.tokens[:10], y.tokens[:10])
                   for x, y in zip(a, b))


@pytest.mark.parametrize("name", MIXES)
def test_each_prompt_extends_the_one_before(name):
    mix = schedule.load_mix(name)
    for s in schedule.build(mix, 3, 1000):
        for k in range(1, len(s.prompt_lens)):
            prev, cur = s.prompt(k - 1), s.prompt(k)
            assert cur[:len(prev)] == prev
            assert len(cur) - len(prev) == mix["append_tokens"]


@pytest.mark.parametrize("name", MIXES)
@pytest.mark.parametrize("capacity", [None, 3000])
def test_every_shape_run_is_warmed(name, capacity):
    """Also with a store far too small, where every kind of miss occurs."""
    mix = schedule.load_mix(name)
    eng, sessions, reqs = _simulate(mix, 5, 20.0, capacity)
    assert reqs and not any(r.failed for r in reqs)
    assert set(eng.shapes) <= set(schedule.warm_shapes(mix))
    # sessions are in mid-conversation when the window opens
    if mix["turns"] > 1:
        assert any(0 < s.history_turns < mix["turns"] for s in sessions)


def test_driver_times_requests_from_when_they_were_due():
    mix = schedule.load_mix("agent")
    _, _, reqs = _simulate(mix, 9, 20.0)
    for r in reqs:
        assert 0 <= r.due < 20.0
        assert r.t_call >= r.due - 1e-9
        assert r.t_first >= r.t_call and r.t_end >= r.t_first
        assert r.ttft_s == pytest.approx(r.t_first - r.due)
    by_sid = {}
    for r in reqs:
        by_sid.setdefault(r.sid, []).append(r)
    for rs in by_sid.values():       # a session's turns in order, one at a time
        for a, b in zip(rs, rs[1:]):
            assert b.turn == a.turn + 1 and b.due >= a.t_end
