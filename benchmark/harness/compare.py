"""The comparison that decides `correct`: the program's class scores
against the plain reference's, on held-out rows."""

from __future__ import annotations

import hashlib
import os

import numpy as np


def reference_scores(run, train: dict, heldout_x: np.ndarray, given: dict) -> np.ndarray:
    """The reference's scores for this configuration, seed and data.
    Kept in the checkout between runs, keyed by the reference file's
    hash, the configuration, the seed and the data themselves (two cells
    of one configuration ask different questions of the same seed and
    sizes): the same question has the same answer, and the chip time
    goes to the program."""
    with open(run.reference.__file__, "rb") as f:
        digest = hashlib.sha256(f.read())
    digest.update(repr((sorted(run.config.items(), key=str), run.seed)).encode())
    arrays = [train["x"], train["y"], heldout_x] + [given[k] for k in sorted(given)]
    for a in arrays:
        a = np.ascontiguousarray(a)
        digest.update(repr((a.shape, a.dtype.str)).encode())
        digest.update(a.data)
    path = os.path.join(run.state_dir, "reference", digest.hexdigest()[:32] + ".npy")
    if os.path.isfile(path):
        return np.load(path)
    answers = run.reference.reference_scores(run.config, run.seed, train, heldout_x, given)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    tmp = path + f".{os.getpid()}.tmp"
    with open(tmp, "wb") as f:
        np.save(f, answers)
    os.replace(tmp, path)
    return answers


def score_error(program: np.ndarray, reference: np.ndarray) -> float:
    """max |program - reference| over max |reference|."""
    if program.shape != reference.shape:
        raise ValueError(f"shapes differ: {program.shape} against {reference.shape}")
    scale = float(np.max(np.abs(reference)))
    return float(np.max(np.abs(program - reference))) / max(scale, 1e-30)


def compare_scores(run, program: np.ndarray, reference: np.ndarray) -> list[str]:
    """What is wrong with the program's scores; nothing if they agree
    with the reference within the configuration's written tolerance."""
    if not np.isfinite(program).all():
        return ["the program's scores are not finite"]
    if not np.isfinite(reference).all():
        return ["the reference's scores are not finite"]
    tolerance = run.config["tolerance"]["scores_max_abs_over_ref_max_abs"]
    error = score_error(program, reference)
    agree = float(np.mean(np.argmax(program, 1) == np.argmax(reference, 1)))
    run.say(
        f"scores against the reference on {len(reference)} held-out rows: "
        f"error {error:.3e} (tolerance {tolerance:.1e}), same top class on {agree:.4f}"
    )
    if error > tolerance:
        return [f"scores differ from the reference by {error:.3e}, over the tolerance {tolerance:.1e}"]
    return []
