"""The plain reference: a direct-form integer FIR.

``y[r, t] = sum_k q[r, k] * x[t + k]`` over the valid outputs of a signal
``x`` — the semantics the served path promises, bit for bit.  Nothing
here imports the program.

`fir_direct` is the definition, in numpy int64.  `fir_exact` computes the
same integers through a float64 matrix product, which is exact while
every product and partial sum stays below 2**53: it checks that bound
(taps · max|q| · max|x|) and refuses otherwise.
"""
from __future__ import annotations

import numpy as np

ROW_BLOCK = 1024  # filters per matrix product: bounds the host memory
OUT_BLOCK = 16384  # outputs per matrix product, likewise


def fir_direct(x: np.ndarray, q: np.ndarray) -> np.ndarray:
    """(R, n − taps + 1) int64 outputs of the (R, taps) bank ``q`` on the
    1-D signal ``x``, one multiply-add per tap."""
    x = np.asarray(x, np.int64)
    q = np.atleast_2d(np.asarray(q, np.int64))
    taps = q.shape[1]
    n_out = x.shape[0] - taps + 1
    y = np.zeros((q.shape[0], max(n_out, 0)), np.int64)
    for k in range(taps):
        y += q[:, k:k + 1] * x[None, k:k + n_out]
    return y


def exact_bound_holds(x: np.ndarray, q: np.ndarray) -> bool:
    bound = (q.shape[1] * float(np.abs(q).max(initial=0))
             * float(np.abs(np.asarray(x, np.int64)).max(initial=0)))
    return bound < 2.0 ** 53


def fir_exact(x: np.ndarray, q: np.ndarray) -> np.ndarray:
    """`fir_direct`'s integers, through float64 products of window blocks
    (exact under the 2**53 bound, which is checked)."""
    q = np.atleast_2d(np.asarray(q, np.int64))
    if not exact_bound_holds(x, q):
        raise ValueError("values too wide for an exact float64 product")
    taps = q.shape[1]
    x = np.asarray(x).astype(np.float64)
    n_out = max(x.shape[0] - taps + 1, 0)
    qd = q.astype(np.float64)
    out = np.empty((q.shape[0], n_out), np.int64)
    for t in range(0, n_out, OUT_BLOCK):
        m = min(OUT_BLOCK, n_out - t)
        w = np.lib.stride_tricks.sliding_window_view(
            x[t:t + m + taps - 1], taps).T.copy()  # (taps, m)
        for r in range(0, q.shape[0], ROW_BLOCK):
            out[r:r + ROW_BLOCK, t:t + m] = np.rint(qd[r:r + ROW_BLOCK] @ w)
    return out


def compare(got: np.ndarray, x: np.ndarray,
            q: np.ndarray) -> tuple[int, int, int]:
    """(outputs that differ from ``ref``, outputs missing, outputs
    expected) for the outputs ``got`` of the (R, taps) bank ``q`` on the
    1-D signal ``x``.  ``got`` is (R, n); outputs beyond the signal's
    valid ones count as differing, a wrong number of rows as all
    missing."""
    q = np.atleast_2d(np.asarray(q, np.int64))
    rows, taps = q.shape
    want_n = np.asarray(x).shape[0] - taps + 1
    expected = rows * want_n
    got = np.asarray(got)
    if got.ndim != 2 or got.shape[0] != rows:
        return 0, expected, expected
    n = min(got.shape[1], want_n)
    bad = rows * (got.shape[1] - n)
    if n:
        bad += int(np.count_nonzero(got[:, :n] != fir_exact(x[:n + taps - 1], q)))
    return bad, rows * (want_n - n), expected
