"""Traffic kind `fit_loop`: a closed loop of whole fits, one client.

Each fit is a new Pipeline over the next of K seeded host-resident data
sets, so that no fitted prefix, cache entry or profile of the program can
answer it. The previous fitted pipeline is dropped before the next fit
starts, so device memory does not grow through the window.
"""

from __future__ import annotations

import time

from benchmark.harness import compare


def setup(run) -> dict:
    rows = run.traffic.get("rows") or run.config["rows"]
    k = run.traffic["datasets"]
    data = [run.sut.make_data(run.config, run.seed, rows, i) for i in range(k)]
    heldout = run.sut.make_data(run.config, run.seed, run.config["heldout_rows"], k)
    # Warm-up: this cell's shapes and no others, but every data set the
    # window will use (what a fit compiles may depend on its data).
    for i, d in enumerate(data):
        t = time.perf_counter()
        run.sut.fit(run.config, d, run.seed)
        run.say(f"warm-up fit {i}: {time.perf_counter() - t:.2f} s")
    return {"data": data, "heldout": heldout, "rows": rows, "last": None}


def window(run, state: dict) -> list:
    k = len(state["data"])

    def fit(i):
        state["last"] = None  # drop the previous fit before the next starts
        state["last"] = (run.sut.fit(run.config, state["data"][i % k], run.seed), i % k)

    return run.closed_loop("fit", state["rows"], fit)[0]


def check(run, state: dict) -> list[str]:
    """The last completed fit against the plain reference, on held-out
    rows; and the program's own signs of a fit gone wrong."""
    if state["last"] is None:
        return ["the last fit failed"]
    fitted, index = state["last"]
    problems = run.sut.health(fitted)
    x = state["heldout"]["x"]
    program = run.sut.scores(run.config, fitted, x, run.seed)
    given = run.sut.given(fitted)
    state["last"] = fitted = None  # the reference needs the chip's memory
    reference = compare.reference_scores(run, state["data"][index], x, given)
    return problems + compare.compare_scores(run, program, reference)
