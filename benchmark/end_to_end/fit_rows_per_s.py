"""fit_rows_per_s: training rows fitted per second: the rows of all
completed fits over the time from the first fit's start to the last
completed fit's end (its weights ready on the device)."""


def value(run) -> float:
    return run.rows_per_s()
