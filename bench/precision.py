"""Arithmetic of the plain reference, at the precision it is computed in.

The reference is float64 numpy.  The control (see ``bench/calibrate.py``)
is the same reference one step of precision lower than the program runs:
each matrix product rounds its operands first, and each result is stored
rounded, as low-precision hardware computes with a float32 accumulator.
"""

from __future__ import annotations

import ml_dtypes
import numpy as np


class Precision:
    """``mm`` multiplies, ``op`` rounds an operand, ``store`` a result."""

    def __init__(self, name: str):
        if name not in PRECISIONS:
            raise KeyError(f"unknown precision {name!r}; known: "
                           f"{sorted(PRECISIONS)}")
        self.name = name
        self._op, self._store = PRECISIONS[name]

    def op(self, x: np.ndarray) -> np.ndarray:
        return self._op(np.asarray(x, np.float64))

    def store(self, x: np.ndarray) -> np.ndarray:
        return self._store(np.asarray(x, np.float64))

    def mm(self, a: np.ndarray, b: np.ndarray) -> np.ndarray:
        return self.store(self.op(a) @ self.op(b))


def _identity(x):
    return x


def _cast(dtype):
    return lambda x: x.astype(dtype).astype(np.float64)


def _fp8_scaled(x):
    """float8 e4m3 with one scale per tensor, as fp8 matmuls are fed."""
    amax = float(np.max(np.abs(x))) if x.size else 0.0
    if amax == 0.0:
        return x
    scale = amax / float(ml_dtypes.finfo(ml_dtypes.float8_e4m3fn).max)
    return (x / scale).astype(ml_dtypes.float8_e4m3fn).astype(
        np.float64) * scale


PRECISIONS = {
    "float64": (_identity, _identity),
    "bfloat16": (_cast(ml_dtypes.bfloat16), _cast(ml_dtypes.bfloat16)),
    "float8_e4m3": (_fp8_scaled, _cast(ml_dtypes.bfloat16)),
}


def segment_sum(values: np.ndarray, segments: np.ndarray,
                num_segments: int) -> np.ndarray:
    out = np.zeros((num_segments,) + values.shape[1:], np.float64)
    np.add.at(out, segments, values)
    return out


def max_error(got: np.ndarray, ref: np.ndarray) -> float:
    """max |got - ref| over max |ref|: the gap compared for ``correct``.

    A wrong shape or a non-finite value reads as infinitely wrong.
    """
    got = np.asarray(got, np.float64)
    if got.shape != ref.shape or not np.all(np.isfinite(got)):
        return float("inf")
    scale = max(float(np.max(np.abs(ref))), 1e-30)
    return float(np.max(np.abs(got - ref))) / scale
