"""Modelled carbon of the window (bench/carbon.py) over the requests
completed in it: busy seconds are the union of ``generate`` calls within
the window, the rest of the window is idle."""
from bench.carbon import window_carbon_g
from bench.metrics._requests import busy_intervals, completed_in_window
from bench.quantile import union_length


def read(run):
    done = len(completed_in_window(run))
    if not done:
        return None
    busy = union_length(busy_intervals(run), 0.0, run.seconds)
    return 1000.0 * window_carbon_g(busy, run.seconds) / done
