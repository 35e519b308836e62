"""The numbers that decide ``correct``, and the verdict against limits.

Both the program and the reference reduce their first three steps to one
observation (see ``bench/steps``):

- ``loss``: (3, items), optional -- each step's loss of each item;
- ``grad``: (leaves,) -- the norm of each leaf of the first gradient as
  the optimizer got it, worked out from the state after step 1;
- ``gproj``: (leaves,) -- the same leaves projected on fixed random probes
  (:func:`project`).  A norm hides errors that are small and random, as
  those of a lower matmul precision are; a projection on a random
  direction keeps each element's relative error;
- ``change``: (leaves,) -- the norm of each leaf's change over the three
  steps, from the state step 4 starts from;
- ``y`` / ``dx``: whole raw tensors -- forward outputs and input
  gradients of the checked steps (:data:`ELEMENTWISE`).

Each number is a gap by the worst item: ``|program - reference|`` over the
reference's own magnitude for that item, or the median item's magnitude
where that is larger (some items are all but zero by chance).  For the
norms the gap is between the two norms, not the norm of the difference.
A leaf whose reference gradient is under a thousandth of the median
leaf's moves by round-off alone and is left out of ``change``.
"""

from __future__ import annotations

import math

import numpy as np

#: a leaf whose reference gradient norm is under this share of the median
#: leaf's is left out of ``change``.
STILL_LEAF = 1e-3

#: the observation key behind each compared number.
NUMBERS = {"loss": "loss", "grad": "grad", "grad_proj": "gproj",
           "update": "change"}

#: numbers compared element by element: ``max |p - r| / max |r|`` of each
#: tensor, worst over the tensors.  Every other number ends in a long
#: float32 reduction (a loss, a weight gradient over the whole batch)
#: whose accumulation order alone moves it by about 1e-5, as much as three
#: bfloat16 passes do; an output element sums a few hundred products.
ELEMENTWISE = {"fwd": "y", "bwd": "dx"}


def norms(leaves) -> np.ndarray:
    return np.array([np.linalg.norm(np.asarray(a, np.float64))
                     for a in leaves])


def project(leaves) -> np.ndarray:
    """Each leaf's inner product, in float64, with a probe of N(0, 1)
    entries that depends only on the leaf's place and shape."""
    out = []
    for i, a in enumerate(leaves):
        a = np.asarray(a, np.float64)
        probe = np.random.default_rng(i).standard_normal(a.shape)
        out.append(float(np.sum(a * probe)))
    return np.array(out)


def item_gaps(prog, ref) -> np.ndarray:
    """``|p_i - r_i| / max(|r_i|, median_j |r_j|)`` per item of the last
    axis, worst over the leading ones.  Not finite reads as infinite."""
    p = np.atleast_2d(np.asarray(prog, np.float64))
    r = np.atleast_2d(np.asarray(ref, np.float64))
    if p.shape != r.shape:
        raise ValueError(f"program {p.shape} vs reference {r.shape}")
    if not np.all(np.isfinite(p)):
        return np.full(p.shape[-1], math.inf)
    floor = np.median(np.abs(r), axis=-1, keepdims=True)
    scale = np.maximum(np.abs(r), floor)
    return np.max(np.abs(p - r) / np.maximum(scale, 1e-300), axis=0)


def worst_gap(prog, ref) -> float:
    """The worst of :func:`item_gaps`."""
    return float(np.max(item_gaps(prog, ref)))


def element_gap(prog, ref) -> float:
    """``max |p - r| / max |r|`` of each tensor, worst over the tensors; a
    tensor of another shape, or not finite, reads as infinite.  In float32, where the tensors are: the difference of two close floats
    is exact, and the tensors are large."""
    worst = 0.0
    for p, r in zip(prog, ref, strict=True):
        p, r = np.asarray(p), np.asarray(r)
        if p.shape != r.shape or not np.all(np.isfinite(p)):
            return math.inf
        worst = max(worst, float(np.max(np.abs(p - r)))
                    / max(float(np.max(np.abs(r))), 1e-300))
    return worst


def numbers(prog: dict, ref: dict) -> dict[str, float]:
    """Every compared number the observations hold."""
    out = {name: element_gap(prog[key], ref[key])
           for name, key in ELEMENTWISE.items() if key in ref}
    for name, key in NUMBERS.items():
        if key not in ref:
            continue
        p, r = np.asarray(prog[key]), np.asarray(ref[key])
        if key == "change":
            g = np.asarray(ref["grad"], np.float64)
            moving = g >= STILL_LEAF * np.median(g)
            p, r = p[moving], r[moving]
        out[name] = worst_gap(p, r)
    return out


def verdict(nums: dict[str, float], limits: dict[str, float]) \
        -> tuple[bool, dict]:
    """``(correct, checks)``: each limited number beside its limit, and
    whether every one is within it."""
    missing = sorted(set(limits) - set(nums))
    if missing:
        raise KeyError(f"limits name numbers this cell does not compute: "
                       f"{missing}")
    checks = {k: {"value": nums[k], "limit": limits[k]} for k in limits}
    ok = all(c["value"] <= c["limit"] for c in checks.values())
    return ok, checks
