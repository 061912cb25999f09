"""Pallas TPU kernels for the BLMAC hot spots, with jnp oracles.

  blmac_fir       — pulse-specialized bit-layer FIR (the paper's machine,
                    lane-parallelized; exact int32), LRU program cache
  blmac_fir_bank  — sparsity-scheduled bank kernel: occupancy-grouped
                    bank tiles, layer-skip superlayer schedules, one
                    integer matmul per populated superlayer (the
                    1.98M-filter sweep path); B=1 fast-paths to the
                    specialized program
  blmac_matmul    — CSD-P pulse-code quantized matmul (serving-side weight
                    decompression; attacks the decode memory roofline)
"""
from .ops import (
    blmac_fir,
    blmac_fir_bank,
    default_interpret,
    pulse_dequantize,
    pulse_matmul_op,
    pulse_quantize,
)
from .blmac_fir import (BankSchedule, pack_bank_trits, plan_bank_schedule,
                        superlayer_schedule)
from . import ref

__all__ = [
    "blmac_fir",
    "blmac_fir_bank",
    "BankSchedule",
    "pack_bank_trits",
    "plan_bank_schedule",
    "superlayer_schedule",
    "default_interpret",
    "pulse_dequantize",
    "pulse_matmul_op",
    "pulse_quantize",
    "ref",
]
