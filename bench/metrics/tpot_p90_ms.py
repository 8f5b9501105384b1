"""90th percentile over requests of the time per output token: from the
first token until the request returns, over its decode steps."""
from bench.quantile import percentile


def read(run):
    return 1000.0 * percentile([r.tpot_s for r in run.requests], 90)
