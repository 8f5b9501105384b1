"""Whether what the timed path served is right: the comparison that
decides ``correct``.

Once the window has closed and the program's state is freed, a sample of
the requests it finished, drawn from the seed, goes through the plain
float32 reference of the configuration's kind
(``bench/references/<kind>.py``): each prompt followed by the tokens served
for it. The number compared is the widest gap by which a served token's
reference logit lies below the reference's best logit at that position (0
where every served token is the reference's own choice).
The sample always holds the longest request, a request whose prefix was
restored from the store (the hit path's prefill) and one that was
prefilled whole (the miss path), where the window has them; every served
token after the first comes from decoding through the cache.

``verdict`` holds every number compared to its limit and decides
``correct``. The control goes through the same comparison and the same
verdict: ``control_tokens`` gives, at each position of the same served
sequences, the token that the reference computed one precision step below
bfloat16 (fp8 e4m3 weights and activations into every matmul) puts first,
and those tokens are judged in place of the served ones.
"""
from __future__ import annotations

import importlib

import numpy as np


def draw_sample(requests, seed: int, k: int):
    """Up to ``k`` served requests: the longest, a hit and a miss where
    there are such, the rest drawn from the seed."""
    pool = [r for r in requests if not r.failed and r.tokens]
    if not pool:
        return []
    rng = np.random.default_rng([seed, 0xC0DE])
    pick = [max(pool, key=lambda r: r.prompt_len + len(r.tokens))]
    for want in (lambda r: r.reused > 0, lambda r: r.reused == 0):
        cands = [r for r in pool if want(r) and r not in pick]
        if cands and not any(want(r) for r in pick):
            pick.append(cands[rng.integers(len(cands))])
    rest = [r for r in pool if r not in pick]
    extra = rng.permutation(len(rest))[:max(k - len(pick), 0)]
    return pick + [rest[i] for i in sorted(extra)]


def _sequences(sample, prompt_of):
    seqs, positions, served = [], [], []
    for r in sample:
        p = prompt_of(r)
        seqs.append(p + r.tokens[:-1])
        positions.append(list(range(len(p) - 1, len(p) - 1 + len(r.tokens))))
        served.append(r.tokens)
    return seqs, positions, served


def _gaps(ref_logits, chosen):
    """Per position: the reference's best logit minus its logit for the
    chosen token."""
    out = []
    for lg, toks in zip(ref_logits, chosen):
        out.extend((lg.max(axis=-1) - lg[np.arange(len(toks)), toks]).tolist())
    return out


def reference_module(conf: dict):
    return importlib.import_module(
        f"bench.references.{conf['dims']['kind']}")


def served_gap(conf: dict, seed: int, sample, prompt_of, pad_to: int = 0,
               chosen=None):
    """Widest gap of the served tokens of ``sample`` below the reference's
    best, and the number of tokens compared. ``chosen`` (one token list
    per request) is judged in place of the served tokens, at the same
    positions of the same sequences."""
    ref = reference_module(conf)
    seqs, pos, served = _sequences(sample, prompt_of)
    lg = ref.logits_at(seed, conf["dims"], seqs, pos, pad_to=pad_to)
    gaps = _gaps(lg, served if chosen is None else chosen)
    return max(gaps), len(gaps)


def control_tokens(conf: dict, seed: int, sample, prompt_of,
                   pad_to: int = 0, quant: str = "fp8"):
    """The control: at each served position of ``sample``, the token that
    the reference computed at ``quant`` puts first."""
    ref = reference_module(conf)
    seqs, pos, _ = _sequences(sample, prompt_of)
    low = ref.logits_at(seed, conf["dims"], seqs, pos, pad_to=pad_to,
                        quant=quant)
    return [lg.argmax(axis=-1).tolist() for lg in low]


def verdict(gap: float, n_tokens: int, window_compiles: int,
            limits: dict):
    """Each number compared, with its limit, and whether all hold."""
    checks = {
        "logit_gap": {"value": gap if np.isfinite(gap) else None,
                      "limit": limits["logit_gap"]},
        "window_compiles": {"value": window_compiles, "limit": 0},
    }
    correct = bool(n_tokens) and all(
        c["value"] is not None and c["value"] <= c["limit"]
        for c in checks.values())
    return checks, correct
