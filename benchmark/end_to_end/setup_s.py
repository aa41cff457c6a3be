"""setup_s: process start to the first measured operation: imports, data
from the seed, warm-up, compilation or loading from the compile cache."""


def value(run) -> float:
    return run.setup_s
