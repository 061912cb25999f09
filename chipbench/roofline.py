"""Work a bank push needs, from its shapes alone, and the chip's peaks.

A push of ``chunk`` samples on C channels through a B-filter bank of
``taps`` taps (type I, so ``taps // 2 + 1`` distinct coefficients after
the symmetric fold) must at least

* read the new input once, at the configuration's sample width;
* read the coefficients once, at the configuration's coefficient width;
* write B · C · n_out int32 outputs;
* do 2 · B · C · n_out · (taps // 2 + 1) integer operations.

Its least time on a chip is the larger of bytes over peak HBM bandwidth
and operations over the peak int8 rate.  At every configuration here the
bytes bound it: each output costs 4 bytes and about taps operations, and
393e12 / 819e9 ≈ 480 operations per byte.
"""
from __future__ import annotations

import json
import pathlib

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str, path: pathlib.Path = PEAKS) -> dict:
    """The published peaks of one chip of ``device_kind``; a kind missing
    from the table is an error."""
    with open(path) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{path.name}; known: {sorted(table)}")
    return table[device_kind]


def push_work(b: int, c: int, n_out: int, taps: int, sample_bits: int = 8,
              coeff_bits: int = 16) -> tuple[float, float]:
    """(operations, bytes) one push needs at least."""
    ops = 2.0 * b * c * n_out * (taps // 2 + 1)
    nbytes = (4.0 * b * c * n_out + c * n_out * sample_bits / 8
              + b * taps * coeff_bits / 8)
    return ops, nbytes


def least_seconds(ops: float, nbytes: float, peak: dict) -> float:
    return max(nbytes / peak["hbm_bytes_per_s"], ops / peak["int8_ops"])
