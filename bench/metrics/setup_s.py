"""Seconds from the start of the process to the opening of the window:
start-up, weights, compiling or loading every program, warming each shape
and serving the history that puts sessions in mid-conversation."""


def read(run):
    return run.setup_s
