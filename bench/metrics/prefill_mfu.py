"""The whole prefill step's share of the chip's peak: the FLOPs the
algorithm needs for every request's uncached tokens (bench/work.py), over
the engine's prefill seconds times the peak bf16 rate (bench/peaks.py)."""
from bench import work
from bench.metrics._requests import served


def read(run):
    rs = [r for r in served(run) if r.computed]
    secs = sum(r.prefill_time_s for r in rs)
    if not secs:
        return None
    flops = sum(work.prefill_flops(run.dims, r.computed, r.reused)
                for r in rs)
    return 100.0 * flops / (secs * run.peaks["bf16_flops"])
