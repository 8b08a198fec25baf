"""The numbers that decide ``correct``, from the program's readings and
the reference's."""
from __future__ import annotations

import statistics
from typing import Dict, Sequence, Set

import numpy as np

# a leaf whose reference gradient is under this share of the median
# leaf's moves under Adam by round-off alone
NOUGHT_SHARE = 1e-3


def moving_leaves(ref_grad: Dict[str, float]) -> Set[str]:
    med = statistics.median(ref_grad.values())
    return {k for k, v in ref_grad.items() if v >= NOUGHT_SHARE * med}


def leaf_gap(prog: Dict[str, float], ref: Dict[str, float],
             leaves: Set[str]) -> float:
    """Worst leaf: the gap between the two norms over the larger of the
    reference's norm of that leaf and of the median leaf."""
    med = statistics.median(ref[k] for k in leaves)
    return max(abs(prog[k] - ref[k]) / max(ref[k], med) for k in leaves)


def leaf_error(prog: Dict[str, np.ndarray], ref: Dict[str, np.ndarray],
               ref_norm: Dict[str, float], leaves: Set[str]) -> float:
    """Worst leaf: the norm of the elementwise difference of two tensors
    over the larger of the reference's norm of that leaf and of the
    median leaf."""
    med = statistics.median(ref_norm[k] for k in leaves)
    return max(float(np.linalg.norm((prog[k] - ref[k]).ravel()))
               / max(ref_norm[k], med) for k in leaves)


def loss_gap(prog: Sequence[float], ref: Sequence[float]) -> float:
    return max(abs(p - r) / abs(r) for p, r in zip(prog, ref))


def train_checks(prog: Dict, ref: Dict) -> Dict[str, float]:
    leaves = moving_leaves(ref["grad"])
    return {"loss_gap": loss_gap(prog["loss"], ref["loss"]),
            "grad_gap": leaf_gap(prog["grad"], ref["grad"], leaves),
            "grad_err": leaf_error(prog["grad_tensors"],
                                   ref["grad_tensors"], ref["grad"], leaves),
            "update_gap": leaf_gap(prog["change"], ref["change"], leaves)}
