"""``ttft_p90_s`` as a per-layer reading, in a cell where it spreads too
widely from run to run to carry a bound: the same 90th percentile over every
request due in the window. What shortens it there (reuse, a faster prefill)
shortens the busy seconds that ``carbon_mg_per_req`` charges."""
from bench.metrics.ttft_p90_s import read  # noqa: F401
