"""The numbers that decide ``correct``, each a gap between the program's
reading and the reference's."""

from __future__ import annotations

import statistics

# A leaf whose first gradient in the reference is under this share of the
# median leaf's is nought to rounding (a key's bias under softmax is one):
# AdamW moves it by round-off alone, so its change is not compared.
STILL_LEAF = 1e-3


def _worst_leaf(got: dict, want: dict, names) -> float:
    """Largest |got - want| over ``names``, each against the larger of the
    reference's own value and the median leaf's."""
    med = statistics.median(want[n] for n in names)
    return max(abs(got[n] - want[n]) / max(want[n], med) for n in names)


def train_numbers(got: dict, ref: dict) -> dict:
    """``got``/``ref``: ``losses`` of the first steps, ``grad_norms`` of the
    first gradient per leaf, ``change_norms`` of each leaf's change after
    the steps."""
    loss_gap = max(abs(g - r) / abs(r) for g, r in zip(got["losses"], ref["losses"]))
    grads = ref["grad_norms"]
    med = statistics.median(grads.values())
    moving = [n for n in grads if grads[n] >= STILL_LEAF * med]
    return {
        "loss_gap": loss_gap,
        "grad_gap": _worst_leaf(got["grad_norms"], grads, list(grads)),
        "change_gap": _worst_leaf(got["change_norms"], ref["change_norms"], moving),
    }


def served_gap(gaps) -> float:
    """The widest gap by which a served token's logit lies below the best."""
    return max(float(g.max()) for g in gaps)

