"""A run drives the whole harness, with only the look for a chip skipped;
with the timed path broken underneath, ``correct`` comes out false. The
cells serve one request at a time on one chip, so of the faults a cell can
have these two apply: a decode step that returns its state unchanged, and
a token altered where it is produced."""
import pytest


def stale_decode(eng):
    inner = eng._decode
    eng._decode = lambda p, c, t, pos: (inner(p, c, t, pos)[0], c)


def altered_token(eng):
    inner = eng.generate
    vocab = eng.cfg.vocab_size

    def generate(*a, **k):
        g = inner(*a, **k)
        if len(g.tokens) > 1:
            g.tokens[1] = (g.tokens[1] + 1) % vocab
        return g
    eng.generate = generate


def test_sound_run_is_correct(tiny_run):
    result, checks, _ = tiny_run()
    assert result["correct"], checks
    assert result["attempted"] > 5 and result["failed"] == 0
    assert set(result["metrics"]) == {"ttft_p90_s", "tpot_p90_ms",
                                      "carbon_mg_per_req", "setup_s"}
    assert list(result)[-1] == "checks"


@pytest.mark.parametrize("fault", [stale_decode, altered_token])
def test_broken_path_is_not_correct(tiny_run, fault):
    result, checks, _ = tiny_run(fault=fault)
    assert not result["correct"], checks
    assert checks["logit_gap"]["value"] > checks["logit_gap"]["limit"]
