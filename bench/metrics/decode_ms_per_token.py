"""Decode time per output token, as the engine times decode (from the
prefill's logits until the last step), over all of the window's requests."""
from bench.metrics._requests import served


def read(run):
    rs = served(run)
    toks = sum(r.num_new for r in rs)
    return 1000.0 * sum(r.decode_time_s for r in rs) / toks if toks \
        else None
