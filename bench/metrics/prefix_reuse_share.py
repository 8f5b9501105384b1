"""Share of prompt tokens served from the store's restored prefixes: the
engine's reused tokens over all prompt tokens of the window's requests."""
from bench.metrics._requests import served


def read(run):
    rs = served(run)
    total = sum(r.prompt_len for r in rs)
    return 100.0 * sum(r.reused for r in rs) / total if total else None
