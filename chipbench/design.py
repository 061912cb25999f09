"""The benchmark's own bank design: the paper's §3.1 grid, quantized by §3.2.

A copy, kept with the benchmark, of the windowed-sinc design (scipy's
``firwin`` construction, vectorized over a bank) and of the row-wise
power-of-two 16-bit quantization.  The program has its own versions
(``repro.filters.firwin_batch``, ``repro.core.po2_quantize_batch``); the
benchmark does not use them, so a change to the program cannot change the
coefficients every cell is measured and checked with.

A configuration file (``configs/<name>.json``) fixes the design: ``taps``,
``n_div`` (the grid's frequency divisions), ``window``, ``coeff_bits`` and
``select`` (``"all"`` rows of the grid, or ``"even"`` for ``n_filters``
rows taken at even intervals).  Designed banks are saved under
``cache/``, keyed by the configuration's design keys.
"""
from __future__ import annotations

import hashlib
import json
import os
import pathlib

import numpy as np

CACHE = pathlib.Path(__file__).resolve().parent / "cache"
WINDOWS = {"hamming": np.hamming, "hann": np.hanning,
           "blackman": np.blackman, "bartlett": np.bartlett,
           "boxcar": np.ones}
DESIGN_KEYS = ("taps", "n_div", "window", "coeff_bits", "select", "n_filters")


def grid_bands(n_div: int) -> list[np.ndarray]:
    """Passbands of the §3.1 grid, in the paper's order: n_div − 1
    low-pass cutoffs i/N, the same as high-pass, then every pair
    (i/N, j/N), i < j, as band-pass and then as band-stop."""
    fs = [i / n_div for i in range(1, n_div)]
    pairs = [(a, b) for i, a in enumerate(fs) for b in fs[i + 1:]]
    bands = [np.array([[0.0, f]]) for f in fs]
    bands += [np.array([[f, 1.0]]) for f in fs]
    bands += [np.array([[a, b]]) for a, b in pairs]
    bands += [np.array([[0.0, a], [b, 1.0]]) for a, b in pairs]
    return bands


def windowed_sinc(taps: int, bands: list[np.ndarray], window: str) -> np.ndarray:
    """(len(bands), taps) float64 filters, each scaled to unit gain at the
    centre of its first passband (scipy.signal.firwin's rule)."""
    if taps % 2 == 0:
        raise ValueError("type-I filters need an odd tap count")
    if window not in WINDOWS:
        raise ValueError(f"unsupported window {window!r}; known: "
                         f"{sorted(WINDOWS)}")
    m = np.arange(taps, dtype=np.float64) - (taps - 1) / 2.0
    h = np.zeros((len(bands), taps))
    for i, b in enumerate(bands):
        for left, right in b:
            h[i] += right * np.sinc(right * m) - left * np.sinc(left * m)
    h *= WINDOWS[window](taps)
    first = np.array([b[0] for b in bands])
    left, right = first[:, 0], first[:, 1]
    centre = np.where(left == 0.0, 0.0, np.where(right == 1.0, 1.0,
                                                 (left + right) / 2))
    gain = (h * np.cos(np.pi * m[None, :] * centre[:, None])).sum(axis=1)
    return h / gain[:, None]


def po2_quantize(h: np.ndarray, bits: int) -> np.ndarray:
    """Row-wise: scale by the largest power of two that keeps every
    coefficient inside a signed ``bits``-bit word, then round half to
    even (§3.2).  Returns int64."""
    lo, hi = -(2 ** (bits - 1)), 2 ** (bits - 1) - 1
    maxabs = np.abs(h).max(axis=1)
    maxabs = np.where(maxabs == 0.0, 1.0, maxabs)
    k = np.floor(np.log2(hi / maxabs))
    while True:
        q = np.rint(h * np.exp2(k)[:, None])
        over = (q.max(axis=1) > hi) | (q.min(axis=1) < lo)
        if not over.any():
            return q.astype(np.int64)
        k = np.where(over, k - 1, k)


def select_rows(n_grid: int, cfg: dict) -> np.ndarray:
    """Grid rows a configuration serves."""
    if cfg["select"] == "all":
        return np.arange(n_grid)
    if cfg["select"] == "even":
        n = int(cfg["n_filters"])
        return ((np.arange(n) + 0.5) * n_grid / n).astype(np.int64)
    raise ValueError(f"unknown select {cfg['select']!r}")


def design(cfg: dict) -> np.ndarray:
    """The configuration's quantized bank, (n_filters, taps) int64."""
    bands = grid_bands(int(cfg["n_div"]))
    rows = select_rows(len(bands), cfg)
    h = windowed_sinc(int(cfg["taps"]), [bands[r] for r in rows],
                      cfg["window"])
    q = po2_quantize(h, int(cfg["coeff_bits"]))
    if q.shape[0] != int(cfg["n_filters"]):
        raise ValueError(f"designed {q.shape[0]} filters, the configuration "
                         f"states {cfg['n_filters']}")
    return q


def design_key(cfg: dict) -> str:
    blob = json.dumps({k: cfg[k] for k in DESIGN_KEYS}, sort_keys=True)
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


def load_bank(cfg: dict, cache: pathlib.Path = CACHE) -> np.ndarray:
    """``design(cfg)``, read from ``cache`` when an earlier run saved it."""
    path = cache / f"{cfg['name']}-{design_key(cfg)}.npy"
    if path.exists():
        return np.load(path)
    q = design(cfg)
    cache.mkdir(parents=True, exist_ok=True)
    tmp = path.with_suffix(f".{os.getpid()}.tmp")
    with open(tmp, "wb") as f:
        np.save(f, q)
    os.replace(tmp, path)
    return q


def checksum(q: np.ndarray) -> str:
    """sha256 of the bank as little-endian int64, row-major."""
    return hashlib.sha256(np.ascontiguousarray(q, "<i8").tobytes()).hexdigest()
