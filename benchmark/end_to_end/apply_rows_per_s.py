"""apply_rows_per_s: rows scored per second over the window, answers
fetched to the host: the rows of all completed requests over the time
from the first request's start to the last completed request's end."""


def value(run) -> float:
    return run.rows_per_s()
