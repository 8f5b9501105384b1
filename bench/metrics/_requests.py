"""Shared selections of a run's requests for the metric readers."""


def served(run):
    """Requests due in the window that were served to the end."""
    return [r for r in run.requests if not r.failed]


def completed_in_window(run):
    return [r for r in served(run) if r.t_end <= run.seconds]


def busy_intervals(run):
    return [(r.t_call, r.t_end) for r in served(run)]
