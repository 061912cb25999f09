"""The paper's cost model (§3.3): additions needed to apply a type-I FIR
filter with a BLMAC, with the symmetric pre-add optimization of Eq. 3.

    tot = N/2                              (pre-adds of symmetric samples)
        + Σ_{j<N/2+1} ntrits[|w_j|]        (BLMAC pulses)

plus the comparison baselines the paper uses, and the session server's
admission model (`predict_session_step_us`).
"""
from __future__ import annotations

import numpy as np

from .csd import csd_digits, num_pulses
from .rle import code_count, code_count_batch

__all__ = [
    "fir_blmac_additions",
    "fir_blmac_additions_batch",
    "adds_per_coeff",
    "adds_per_tap",
    "classical_equivalent_adds",
    "machine_cycles",
    "machine_cycles_batch",
    "predict_session_step_us",
]


def _half(wq: np.ndarray) -> np.ndarray:
    """First N//2 + 1 coefficients of a type-I (odd, symmetric) filter."""
    n = wq.shape[-1]
    if n % 2 == 0:
        raise ValueError("type-I FIR filters have an odd number of taps")
    return wq[..., : n // 2 + 1]


def fir_blmac_additions(wq: np.ndarray) -> int:
    """Total additions to apply one quantized N-tap type-I filter (Eq. 3)."""
    n = wq.shape[-1]
    return int(n // 2 + num_pulses(np.abs(_half(wq))).sum())


def fir_blmac_additions_batch(wq: np.ndarray) -> np.ndarray:
    """Vectorized over a bank: ``wq`` is (n_filters, n_taps) int."""
    n = wq.shape[-1]
    return n // 2 + num_pulses(np.abs(_half(wq))).sum(axis=-1)


def adds_per_coeff(total_adds, n_taps: int):
    """(B_N − N/2) / (N/2 + 1) — comparable to Tab. 3's per-weight averages."""
    return (np.asarray(total_adds, np.float64) - n_taps // 2) / (n_taps // 2 + 1)


def adds_per_tap(total_adds, n_taps: int):
    return np.asarray(total_adds, np.float64) / n_taps


def classical_equivalent_adds(n_taps: int, mult_cost_adds: int = 15) -> int:
    """The paper's apples-to-apples baseline: symmetric classical algorithm
    = (N/2+1) multiplications (@ ``mult_cost_adds`` adds each for 16-bit)
    + N−1 additions."""
    return mult_cost_adds * (n_taps // 2 + 1) + n_taps - 1


def machine_cycles(
    wq: np.ndarray, n_layers: int = 16, overhead: int = 0
) -> int:
    """Clock cycles of the §4 dot-product machine for one output sample:
    one cycle per RLE code (pulse or EOR) + fixed per-sample overhead."""
    digits = csd_digits(_half(wq), n_digits=n_layers)
    return code_count(digits) + overhead


# ---------------------------------------------------------------------------
# multi-tenant session-step cost model (admission control)
# ---------------------------------------------------------------------------
#
# `BankSessionServer` packs active sessions into the C channel lanes of
# ONE shared engine and dispatches once per step; when more sessions are
# active than the engine has lanes, a step takes several rounds.  The
# kernel computes every lane of every round whether or not it carries a
# real tenant (idle lanes are zero-padded), so a round costs the full
# dispatch plus n_slots lane-fills regardless of occupancy — which is
# exactly the asymmetry admission control needs: adding a session is
# nearly free until it spills a new round.  The constants are guesses
# made on a CPU, never measured on the chip: they rank "admit vs
# reject", they do not predict wall time.

SESSION_DISPATCH_US = 800.0  # per bank dispatch round
SESSION_LANE_US = 45.0  # per channel lane staged + sliced, per round
JOURNAL_APPEND_US = 15.0  # per chunk/pull WAL record framed + written
JOURNAL_SYNC_US = 400.0  # per group-commit fsync at the end of a step


def predict_session_step_us(
    dispatch_us: float,
    n_active: int,
    n_slots: int,
    journal_us: float = 0.0,
) -> float:
    """Modelled latency of one session-server batching step with
    ``n_active`` sessions packed into ``n_slots`` shared lanes:
    ceil(n_active / n_slots) rounds, each a full ``dispatch_us`` bank
    dispatch (the server passes `SESSION_DISPATCH_US`) plus the
    per-lane staging cost of every slot in the round, plus the step's
    flat write-ahead-journal bill (``journal_us``, built by the server
    from `JOURNAL_APPEND_US` / `JOURNAL_SYNC_US` when a journal is
    attached).  The server admits a session only while the predicted
    step stays inside its latency budget."""
    if n_slots < 1:
        raise ValueError("n_slots must be >= 1")
    if n_active <= 0:
        return 0.0
    rounds = -(-int(n_active) // int(n_slots))
    return rounds * (float(dispatch_us) + n_slots * SESSION_LANE_US) \
        + float(journal_us)


def machine_cycles_batch(
    wq: np.ndarray,
    n_layers: int = 16,
    overhead: int = 0,
    fused_last_add: bool = False,
) -> np.ndarray:
    """Vectorized :func:`machine_cycles` over a (B, taps) bank → (B,) int64.

    ``fused_last_add`` applies the §4 optimization (the last add of each
    non-empty bit layer overlaps the shift: −1 cycle per such layer, −16
    for a fully-populated 16-layer program) — matching both simulators.
    """
    wq2 = np.atleast_2d(np.asarray(wq, np.int64))
    digits = csd_digits(_half(wq2), n_digits=n_layers)  # (B, M, L)
    cycles = code_count_batch(digits) + overhead
    if fused_last_add:
        cycles = cycles - np.count_nonzero(digits.any(axis=1), axis=-1)
    return cycles
