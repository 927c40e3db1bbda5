"""The numbers that decide ``correct``: what the program produced in its
first three train steps against the reference following the same steps.

* ``logp_gap``: the widest gap between the generator's behaviour
  log-prob of a sampled token and the reference's, over every action
  position of the first batch, all sampled under version-0 weights
  (prefill, paged decode and the sampling kernel);
* ``logp_tail``: the share of those positions whose gap is over the
  cell's ``logp_tail_nats``.  On a TPU the program's float32 matrix
  products run as one bfloat16 pass, so a bfloat16 computation widens
  the gaps by less than three times; their tail beyond a fixed width
  grows far more;
* ``mean_logp_gap``: the gap between the trainer's mean log-prob of the
  first batch's action positions (the train step's own ``mean_logp``,
  the one reading of its per-token log-probs the step returns) and the
  reference's;
* ``loss_gap``: the gap between the trainer's AIPO loss of the first
  step and the reference's;
* ``grad_norm_gap``: the gap between the global norm of the trainer's
  first gradient before clipping (the step's own ``grad_norm``) and the
  reference's, over the reference's;
* ``grad_gap``: the first gradient as Adam holds it (its first moment
  after that step over ``1 - b1``), by the worst leaf: the gap between
  the two norms over the reference's norm of that leaf or of the median
  leaf, whichever is larger;
* ``grad_median_gap``: the same, by the median leaf;
* ``change_gap``: the worst leaf's measure for the change of the
  parameters after three steps, read from the weights the generator
  received as version 3 (so the weight plane is inside the check).

"The first gradient" is that of the first of the three steps whose
batch had a reward spread in some group: before it every gradient, and
so Adam's state, is exactly zero on both sides.  Where none of the three
had one, the reference has no gradient, nothing moves, and the gradient
numbers and ``change_gap`` are ``None``: not compared.

The loss and the mean log-prob are read at the first step, where both
sides start from the same weights: from the second on each side follows
its own updated weights, which have already moved apart
(``change_gap`` reads how far), and the later steps' losses and
log-probs differ by that more than by the step's arithmetic.

Leaves whose reference gradient is under a thousandth of the median
leaf's (a key bias under softmax is one) only move by round-off and are
left out of the leaf numbers: by the first gradient for ``grad_gap`` and
``grad_median_gap``, by the largest of the three steps' for the change.
"""
from __future__ import annotations

import numpy as np

NAMES = ("logp_gap", "logp_tail", "mean_logp_gap", "loss_gap",
         "grad_norm_gap", "grad_gap", "grad_median_gap", "change_gap")
NEGLIGIBLE = 1e-3


def kept_leaves(ref_grad):
    """Leaves the reference's gradient moves (none if it is all zero)."""
    ref_grad = np.asarray(ref_grad, np.float64)
    med = float(np.median(ref_grad))
    return ref_grad > NEGLIGIBLE * med if med > 0 else \
        np.zeros(ref_grad.shape, bool)


def kept_for_change(ref):
    """Leaves any of the followed steps' reference gradients moves."""
    return kept_leaves(np.max(np.stack(ref["grad_norms"]), axis=0))


def leaf_gaps(prog, ref, keep):
    """Each kept leaf's gap of norms over the reference's norm of that
    leaf or of the median kept leaf, whichever is larger."""
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    floor = float(np.median(ref[keep])) if keep.any() else 0.0
    den = np.maximum(ref, floor)
    return np.abs(prog - ref)[keep] / den[keep]


def worst_leaf(prog, ref, keep):
    """Worst leaf's gap of norms, or None where no leaf is kept."""
    gaps = leaf_gaps(prog, ref, keep)
    return float(np.max(gaps)) if gaps.size else None


def first_gradient(ref):
    """The first followed step whose reference gradient is not zero."""
    return next((k for k, g in enumerate(ref["grad_norm"]) if g > 0), None)


def action_gaps(batch, ref_logp):
    """|behaviour - reference log-prob| at every action position of the
    batch, in one flat array."""
    mask = np.asarray(batch["mask"])[:, 1:] > 0
    blp = np.asarray(batch["behavior_logp"])[:, 1:]
    return np.abs(blp - ref_logp)[mask]


def readings(prog, ref, tail_nats):
    """The numbers, from the program's captures (``batches``, ``losses``,
    ``mean_logp``, ``grad_norm`` and ``moments`` of each step,
    ``change``) and a reference result of ``replay.follow``."""
    gap = action_gaps(prog["batches"][0], ref["logp"])
    out = {"logp_gap": float(np.max(gap)),
           "logp_tail": float(np.mean(gap > tail_nats)),
           "mean_logp_gap": abs(float(prog["mean_logp"][0])
                                - ref["mean_logp"][0]),
           "loss_gap": abs(float(prog["losses"][0]) - ref["losses"][0]),
           "grad_norm_gap": None, "grad_gap": None, "grad_median_gap": None,
           "change_gap": worst_leaf(prog["change"], ref["change"],
                                    kept_for_change(ref))}
    k = first_gradient(ref)
    if k is not None:
        gaps = leaf_gaps(prog["moments"][k], ref["grad_norms"][k],
                         kept_leaves(ref["grad_norms"][k]))
        out["grad_norm_gap"] = (abs(float(prog["grad_norm"][k])
                                    - ref["grad_norm"][k])
                                / ref["grad_norm"][k])
        out["grad_gap"] = float(np.max(gaps))
        out["grad_median_gap"] = float(np.median(gaps))
    return out


def panel(prog, ref):
    """Candidate statistics, for the readings that set the limits: the
    action-position gap's tails at several widths, each step's gap of
    loss and of mean log-prob, and the first gradient's and the change's
    gap by leaf."""
    gap = action_gaps(prog["batches"][0], ref["logp"])
    out = {"positions": int(gap.size),
           "rms": float(np.sqrt(np.mean(gap * gap)))}
    for t in (0.03, 0.04, 0.05, 0.06, 0.08, 0.1):
        out[f"tail_{t}"] = float(np.mean(gap > t))
    out["loss_steps"] = [abs(float(a) - b) for a, b in
                         zip(prog["losses"], ref["losses"])]
    out["mean_logp_steps"] = [abs(float(a) - b) for a, b in
                              zip(prog["mean_logp"], ref["mean_logp"])]
    k = first_gradient(ref)
    out["first_gradient_step"] = k
    if k is not None:
        out["grad_leaves"] = leaf_gaps(
            prog["moments"][k], ref["grad_norms"][k],
            kept_leaves(ref["grad_norms"][k])).tolist()
    out["change_leaves"] = leaf_gaps(prog["change"], ref["change"],
                                     kept_for_change(ref)).tolist()
    return out


def judge(values, limits):
    """(correct, {name: {"value", "limit"}}) for the numbers the cell's
    limits file compares, in the order of NAMES.  A number without a
    limit there is read and printed but not compared: PERF.md gives its
    readings and why no limit could hold.  A number the reference leaves
    undefined (``None``) is not compared either."""
    out = {n: {"value": values[n], "limit": limits[n]} for n in NAMES
           if n in limits and values[n] is not None}
    return all(v["value"] <= v["limit"] for v in out.values()), out
