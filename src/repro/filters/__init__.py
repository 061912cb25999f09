"""FIR filter substrate: vectorized windowed-sinc design (scipy-compatible),
the paper's 1.98M-filter sweep, exact reference application paths, and the
streaming overlap-save filter-bank engine."""
from .apply import (fir_bit_layers, fir_bit_layers_batch, fir_direct,
                    fir_symmetric, sliding_windows)
from .bank import FilterBankEngine
from .fir import (FilterKind, bands_for, design_bank, firwin_batch,
                  spread_lowpass_qbank, window_values)
from .sharded import ShardedFilterBankEngine
from .sweep import TAPS_RANGE, SweepSpec, iter_sweep, sweep_bank, sweep_specs

__all__ = [
    "FilterBankEngine",
    "ShardedFilterBankEngine",
    "fir_bit_layers",
    "fir_bit_layers_batch",
    "fir_direct",
    "fir_symmetric",
    "sliding_windows",
    "FilterKind",
    "bands_for",
    "design_bank",
    "firwin_batch",
    "spread_lowpass_qbank",
    "window_values",
    "TAPS_RANGE",
    "SweepSpec",
    "iter_sweep",
    "sweep_bank",
    "sweep_specs",
]
