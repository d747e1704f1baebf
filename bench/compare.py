"""The numbers that decide `correct`, each a gap to the plain reference.

Training: each compared iteration's loss as a relative gap; the gradient
as the optimizer got it (Adam's first moment after the first iteration)
and the parameters' change after the compared iterations, each by its
worst leaf: the gap between the program's norm and the reference's norm
of that leaf, over the larger of the reference's norm of that leaf and of
the median leaf.  Leaves whose reference gradient is under a thousandth
of the median leaf's move by rounding alone and are left out.
Serving: the widest gap of a served action or value to the reference.
"""
from __future__ import annotations

import jax
import numpy as np

ZERO_GRAD_SHARE = 1e-3


def _norms(tree) -> list[float]:
    return [float(np.linalg.norm(np.asarray(x, np.float64)))
            for x in jax.tree.leaves(tree)]


def kept_leaves(ref_grad) -> list[bool]:
    norms = _norms(ref_grad)
    med = float(np.median(norms))
    return [n >= ZERO_GRAD_SHARE * med for n in norms]


def leaf_gaps(got, want, keep: list[bool]) -> dict[str, float]:
    """| |got| - |want| | / max(|want|, median) of every kept leaf."""
    g, w = _norms(got), _norms(want)
    med = float(np.median([x for x, k in zip(w, keep) if k]))
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(want)[0]]
    return {path: (abs(a - b) / max(b, med, 1e-30) if np.isfinite(a)
                   else float("inf"))
            for path, a, b, k in zip(paths, g, w, keep) if k}


def worst_leaf_gap(got, want, keep: list[bool]) -> float:
    return float(max(leaf_gaps(got, want, keep).values()))


def relative_gap(got: float, want: float) -> float:
    if not np.isfinite(got):
        return float("inf")
    return abs(got - want) / max(abs(want), 1e-30)


def tree_sub(a, b):
    return jax.tree.map(lambda x, y: np.asarray(x, np.float64)
                        - np.asarray(y, np.float64), a, b)


def training_gaps(*, losses_got, losses_want, m_got, m_want, p0, p_got,
                  p_want) -> dict[str, float]:
    keep = kept_leaves(m_want)
    gaps = {f"loss_gap_{k}": relative_gap(a, b)
            for k, (a, b) in enumerate(zip(losses_got, losses_want))}
    gaps["grad_gap"] = worst_leaf_gap(m_got, m_want, keep)
    gaps["step_gap"] = worst_leaf_gap(tree_sub(p_got, p0),
                                      tree_sub(p_want, p0), keep)
    return gaps


def worst_leaves(*, m_got, m_want, p0, p_got, p_want) -> dict:
    """The three largest leaf gaps of the gradient and of the change."""
    keep = kept_leaves(m_want)
    top = lambda d: sorted(d.items(), key=lambda x: -x[1])[:3]
    return {"grad": top(leaf_gaps(m_got, m_want, keep)),
            "step": top(leaf_gaps(tree_sub(p_got, p0),
                                  tree_sub(p_want, p0), keep))}
