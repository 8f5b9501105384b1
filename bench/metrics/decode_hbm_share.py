"""Decode's share of the chip's memory bandwidth: the bytes every decode
step needs (the weights once, without the embedding table, and the keys and
values of the positions attended; bench/work.py), over the engine's decode
seconds times the peak bandwidth (bench/peaks.py)."""
from bench import work
from bench.metrics._requests import served


def read(run):
    rs = [r for r in served(run) if r.num_new]
    secs = sum(r.decode_time_s for r in rs)
    if not secs:
        return None
    moved = sum(work.decode_bytes(run.dims, r.prompt_len, r.num_new)
                for r in rs)
    return 100.0 * moved / (secs * run.peaks["hbm_bytes_per_s"])
