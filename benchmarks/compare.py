"""The arithmetic that decides ``correct``: gaps between what the timed
path produced and what the plain reference gives.  Kept with the
benchmark so that no later PR can change it."""

from __future__ import annotations

import statistics


def rel_gap(a: float, b: float, floor: float = 0.0) -> float:
    """|a - b| against the larger of |b| and ``floor``."""
    return abs(a - b) / max(abs(b), floor, 1e-30)


def worst_leaf_gap(prog, ref, skip=()):
    """Largest gap between the program's norm of a leaf and the
    reference's, measured against the reference's norm of that leaf or of
    the median leaf, whichever is larger.  Returns (gap, leaf index)."""
    if len(prog) != len(ref):
        raise ValueError(f"{len(prog)} leaves against {len(ref)}")
    med = statistics.median(ref)
    worst, where = 0.0, -1
    for i, (p, r) in enumerate(zip(prog, ref)):
        if i in skip:
            continue
        g = rel_gap(p, r, med)
        if not g == g:  # NaN: never correct
            return float("inf"), i
        if g > worst:
            worst, where = g, i
    return worst, where


def tree_diff(prog_leaves, ref_leaves) -> float:
    """Norm of the difference of two lists of arrays over the norm of the
    second, all leaves taken as one vector.  Unlike a gap of norms it
    sees rounding noise, which is what tells one precision from the next
    below."""
    import jax.numpy as jnp

    num = den = 0.0
    for n, d in leaf_diffs(prog_leaves, ref_leaves):
        num, den = num + n, den + d
    return (num / max(den, 1e-60)) ** 0.5


def leaf_diffs(prog_leaves, ref_leaves):
    """[(squared norm of the difference, squared norm of the second)]."""
    import jax.numpy as jnp

    return [
        (float(jnp.sum(jnp.square(p - r))), float(jnp.sum(jnp.square(r))))
        for p, r in zip(prog_leaves, ref_leaves, strict=True)
    ]


def training_numbers(prog: dict, ref: dict) -> dict:
    """``prog`` and ``ref`` hold ``losses``, ``grad_norms``,
    ``change_norms`` and ``first_grads`` of the first steps (see the
    references).  Leaves whose reference gradient is under a thousandth
    of the median leaf's move by round-off alone and are left out of the
    change."""
    med = statistics.median(ref["grad_norms"])
    quiet = {i for i, g in enumerate(ref["grad_norms"]) if g < 1e-3 * med}
    loss_gap = max(
        (rel_gap(p, r) if p == p else float("inf"))
        for p, r in zip(prog["losses"], ref["losses"], strict=True)
    )
    grad_gap, grad_leaf = worst_leaf_gap(prog["grad_norms"], ref["grad_norms"])
    change_gap, change_leaf = worst_leaf_gap(
        prog["change_norms"], ref["change_norms"], skip=quiet
    )
    return dict(
        loss_gap=loss_gap, grad_gap=grad_gap, change_gap=change_gap,
        grad_diff=tree_diff(prog["first_grads"], ref["first_grads"]),
        _where=dict(grad_leaf=grad_leaf, change_leaf=change_leaf,
                    quiet_leaves=sorted(quiet),
                    leaf_grad_diffs=[
                        round((n / max(d, 1e-60)) ** 0.5, 5) for n, d in
                        leaf_diffs(prog["first_grads"], ref["first_grads"])
                    ]),
    )


def judge(numbers: dict, limits: dict):
    """[(name, value, limit, ok)] for every limit; a number that is
    missing, or not a number, fails.  A limit of ``null`` means the
    number is printed and not compared."""
    rows = []
    for name, limit in limits.items():
        value = numbers.get(name)
        if limit is None:
            rows.append((name, value, None, True))
            continue
        ok = value is not None and value == value and value <= limit
        rows.append((name, value, limit, bool(ok)))
    return rows
