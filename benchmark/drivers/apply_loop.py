"""Traffic kind `apply_loop`: a closed loop of bulk scoring requests, one
client.

The model is fitted once in set-up. A request is `request_rows` host rows
through `FittedPipeline.apply_batch` with the labels fetched back to the
host, rotating over K seeded inputs.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark.harness import compare


def setup(run) -> dict:
    fit_rows = run.config["rows"]
    k = run.traffic["inputs"]
    rows = run.traffic["request_rows"]
    train = run.sut.make_data(run.config, run.seed, fit_rows, 0)
    t = time.perf_counter()
    fitted = run.sut.fit(run.config, train, run.seed)
    run.say(f"set-up fit on {fit_rows} rows: {time.perf_counter() - t:.2f} s")
    inputs = [run.sut.make_data(run.config, run.seed, rows, 1 + i)["x"] for i in range(k)]
    answers = None
    for i, x in enumerate(inputs):  # warm-up: the request's shape, every input
        t = time.perf_counter()
        answers = run.sut.apply(fitted, x)
        run.say(f"warm-up request {i}: {time.perf_counter() - t:.3f} s")
    return {
        "fitted": fitted, "train": train, "inputs": inputs, "rows": rows,
        "last": (answers, k - 1),
    }


def window(run, state: dict) -> list:
    fitted, inputs = state["fitted"], state["inputs"]
    samples, last = run.closed_loop(
        "apply", state["rows"], lambda i: run.sut.apply(fitted, inputs[i % len(inputs)])
    )
    if last is not None:
        state["last"] = (last[0], last[1] % len(inputs))
    return samples


def check(run, state: dict) -> list[str]:
    """No program compiled or loaded inside the window; the last
    request's labels are the argmax of the program's scores; and those
    scores agree with the plain reference's on the first held-out rows."""
    problems = run.sut.health(state["fitted"])
    if run.window_compiles:
        problems.append(
            f"{run.window_compiles} programs compiled or loaded inside the window"
        )
    answers, index = state["last"]
    n = run.config["heldout_rows"]
    x = state["inputs"][index][:n]
    if answers.shape != (state["rows"],):
        problems.append(f"a request returned shape {answers.shape}")
        return problems
    program = run.sut.scores(run.config, state["fitted"], x, run.seed)
    same = float(np.mean(answers[:n] == np.argmax(program, axis=1)))
    if same < 1.0:
        # the request ran at its full size and the scores at 1024 rows:
        # two programs, so only a tie may differ
        top2 = np.sort(program, axis=1)[:, -2:]
        ties = float(np.mean((top2[:, 1] - top2[:, 0]) < 1e-4 * np.abs(top2[:, 1])))
        if 1.0 - same > ties:
            problems.append(f"labels equal the argmax of the scores on only {same:.4f} of rows")
    given = run.sut.given(state["fitted"])
    state["fitted"] = None  # the reference needs the chip's memory
    reference = compare.reference_scores(run, state["train"], x, given)
    return problems + compare.compare_scores(run, program, reference)
