"""``prefill_ms_per_req`` in a cell whose time to first token is too
unsteady to bound: the same reading, named apart because there it moves
``carbon_mg_per_req`` (prefill seconds are busy seconds)."""
from bench.metrics.prefill_ms_per_req import read  # noqa: F401
