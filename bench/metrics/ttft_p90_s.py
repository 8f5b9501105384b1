"""90th percentile of time to first token over every request due in the
window, each timed from when it was due; one never served counts as
infinitely late."""
from bench.quantile import percentile


def read(run):
    return percentile([r.ttft_s for r in run.requests], 90)
