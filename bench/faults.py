"""The faults the comparison has to catch, planted in the timed path.

``bench/readings.py`` reads each at a cell's own size to set the limits,
and ``tests/bench`` sees each come out not correct at a tiny size.
"""

from __future__ import annotations

import contextlib

import jax

#: the relative change of every conv output under :func:`altered_answers`.
ALTERATION = 1e-3


def unchanged_state(program):
    """The step returns the state it was given."""
    def step(state, batch, i):
        return state, program(state, batch, i)[1]
    return step


def half_batch(program):
    """The step leaves out the second half of every batch, so that its
    means are taken over the rest."""
    def step(state, batch, i):
        return program(state, jax.tree.map(lambda a: a[:a.shape[0] // 2],
                                           batch), i)
    return step


@contextlib.contextmanager
def altered_answers():
    """While a step is traced, every conv and transposed conv of the
    program returns its output times ``1 + ALTERATION``, so that every
    pass of it (forward, input grad, weight grad) is off by that much."""
    from repro.core import conv as C
    saved = C.conv2d, C.conv2d_transpose

    def alter(f):
        return lambda *a, **k: f(*a, **k) * (1 + ALTERATION)

    C.conv2d, C.conv2d_transpose = map(alter, saved)
    try:
        yield
    finally:
        C.conv2d, C.conv2d_transpose = saved
