"""Cost-model framework for optimizable operators.

TPU-native re-design of the reference's solver cost models
(reference: nodes/learning/CostModel.scala:6-17,
nodes/learning/LeastSquaresEstimator.scala:17-31). Costs combine cpu
(flops), memory-bandwidth (elements scanned) and network (elements moved
across the mesh) terms:  max(cpu·flops, mem·elems) + network·elems.

Three weight sources, in order of preference:

1. ``measured_tpu_weights()`` — constants fitted on the actual chip by
   ``scripts/solver_comparison.py --fit-constants`` and committed to
   ``tpu_cost_constants.json`` (the analog of the reference's
   constantEstimator.R refit workflow).
2. ``tpu_weights()`` — first-principles v5e numbers, used when no
   measured file exists.
3. ``DEFAULT_COST_WEIGHTS`` — the reference's own constants
   ("determined empirically via results run on a 16 r3.4xlarge node
   cluster"), used on non-TPU backends so relative solver choices match
   the reference's published behavior.

``default_cost_weights()`` picks automatically by jax backend.
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass
from typing import Optional


@dataclass(frozen=True)
class CostWeights:
    cpu: float      # ms per flop
    mem: float      # ms per element scanned (fp32)
    network: float  # ms per element moved across the mesh


# reference: LeastSquaresEstimator.scala:29-31 (16×r3.4xlarge cluster).
# The reference never documents its units; only the ratios matter for the
# argmin over solvers, so these are kept verbatim.
DEFAULT_COST_WEIGHTS = CostWeights(cpu=3.8e-4, mem=2.9e-1, network=1.32)

#: Written by ``scripts/solver_comparison.py --fit-constants`` on-chip.
MEASURED_CONSTANTS_PATH = os.path.join(
    os.path.dirname(__file__), "tpu_cost_constants.json"
)


def tpu_weights() -> CostWeights:
    """First-principles per-unit costs (ms) for one TPU v5e chip.

    Units match the ``cost()`` formulas: flops are raw flop counts,
    mem/network are fp32 element counts (×4 bytes):

    - MXU  ≈ 2.0e14 flop/s → 2.0e11 flop/ms → cpu = 5.0e-12 ms/flop
    - HBM  ≈ 8.2e11 B/s → 2.05e8 elem/ms   → mem ≈ 4.9e-9 ms/elem
    - ICI  ≈ 4.5e10 B/s per link → 1.1e7 elem/ms → net ≈ 8.9e-8 ms/elem
    """
    return CostWeights(cpu=5.0e-12, mem=4.9e-9, network=8.9e-8)


def measured_tpu_weights() -> Optional[CostWeights]:
    """Constants fitted on the chip, if the refit has been run."""
    try:
        with open(MEASURED_CONSTANTS_PATH) as f:
            d = json.load(f)
        return CostWeights(cpu=d["cpu"], mem=d["mem"], network=d["network"])
    except (OSError, KeyError, ValueError):
        return None


def default_cost_weights(backend: Optional[str] = None) -> CostWeights:
    """Pick weights for the active backend: measured-TPU > first-principles
    TPU on accelerators; the reference's cluster constants on CPU (where
    they keep solver choices aligned with the reference's behavior)."""
    if backend is None:
        import jax

        backend = jax.default_backend()  # a backend that fails to start raises
    if backend == "cpu":
        return DEFAULT_COST_WEIGHTS
    return measured_tpu_weights() or tpu_weights()


class CostModel:
    """Mixin: operators expose cost(n, d, k, sparsity, num_machines)."""

    def cost(self, n, d, k, sparsity, num_machines, w=DEFAULT_COST_WEIGHTS) -> float:
        raise NotImplementedError
