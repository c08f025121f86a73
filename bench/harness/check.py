"""The comparison that decides ``correct``.

The program answers in integers on its declared output grid; scaled by
that grid they are real values, which must equal the reference's
exactly: the design claims bit-exactness with the fixed-point network.
An answer that never came, or that raised, is a failed request and
fails the run as a wrong answer does.  Every limit is 0.
"""

from __future__ import annotations

import numpy as np

LIMITS = {"mismatched": 0, "failed": 0}


def output_scale(design) -> np.ndarray:
    """2**exp of each output of the design, shaped like one answer."""
    exps = [q.exp if not q.is_zero else 0 for q in design.out_qints]
    return (2.0 ** np.asarray(exps, np.float64)).reshape(design.out_shape)


def mismatched(answers: np.ndarray, scale: np.ndarray, want: np.ndarray) -> int:
    """How many answers (rows) differ anywhere from the reference's."""
    got = np.asarray(answers, np.float64) * scale
    diff = got != want
    return int(diff.reshape(len(diff), -1).any(axis=1).sum())


def verdict(numbers: dict) -> bool:
    return all(numbers[k] <= LIMITS[k] for k in LIMITS)


def lines(numbers: dict) -> list[str]:
    """One plain line per compared number, with its limit."""
    return [f"check {k} = {numbers[k]} (limit {LIMITS[k]})" for k in LIMITS]


def as_json(numbers: dict) -> dict:
    return {k: {"value": numbers[k], "limit": LIMITS[k]} for k in LIMITS}
