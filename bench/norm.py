"""Device time of a network's batch norms.

The program runs each BatchNorm, with the residual add and the ReLU that
follow it, under the named scope ``batch_norm``; jax writes it into the
``op_name`` of every device op of the norm, wrapped by the transforms
(``jit(step)/transpose(jvp(batch_norm))/mul``).  An op counts as norm when
``batch_norm`` is among the tokens of its path and it is in no conv pass
(``bench.scopes.classify``), so that the two never share an op.  Where
XLA fuses part of a BN into a conv pass's fusion, that part counts as the
conv's.
"""

from __future__ import annotations

import re

from bench import scopes

NORM = "batch_norm"


def is_norm(path: str) -> bool:
    """Whether an ``op_name`` path lies in a norm scope and in no conv
    pass."""
    if scopes.classify(path)[0] is not None:
        return False
    return any(NORM in re.split(r"[/()]", alt) for alt in path.split(";"))


def seconds(view, scope_map: dict[str, str]) -> float:
    """Device seconds of the window's norm ops, averaged over the chips."""
    return sum((b - a) * 1e-9 for _, name, a, b in view.ops
               if is_norm(scope_map.get(name, ""))) / view.chips

