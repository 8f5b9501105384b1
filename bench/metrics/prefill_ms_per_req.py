"""Mean prefill time per request, as the engine times it (from after the
store lookup until the prefill's logits are ready)."""
from bench.metrics._requests import served


def read(run):
    rs = served(run)
    return 1000.0 * sum(r.prefill_time_s for r in rs) / len(rs) if rs \
        else None
