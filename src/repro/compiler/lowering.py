"""`lower(program, backend=...)`: one compiled program, five executables.

Every execution engine in this repo is a *backend* of the same compiled
artifact.  `lower` returns a callable ``exe(x) -> (B, C, n_out)`` (``x``
is ``(C, T)`` or ``(T,)`` integer samples) for:

  * ``"oracle"``      — the numpy Eq. 2 reference
    (`fir_bit_layers_batch`).  Deliberately the naive dense bit-layer
    recursion reading only ``program.qbank``: it is the independent
    ground truth the other backends are differentially verified against,
    so it must not share the schedule mechanism under test.  int64.
  * ``"specialized"`` — per-filter pulse-baked Pallas programs
    (`specialized_program` LRU) from ``program.pulse_schedules()``. int32.
  * ``"scheduled"``   — the sparsity-scheduled bank kernel on
    ``program.packed``, at the geometry `bank_plan` gives (the engines'
    own): Mosaic on a TPU, the Pallas interpreter elsewhere. int32.
  * ``"vmachine"``    — the vectorized §4 machine simulator programmed
    with the bank; the executable exposes ``.vmachine`` and ``.fits``
    (weight-memory verdicts). int64.
  * ``"sharded"``     — a `ShardedFilterBankEngine` built FROM the
    program over a (bank, data) mesh; exposes ``.engine``.  One-shot
    semantics (the engine is reset per call). int32.

All five agree bit-for-bit on integer inputs — `tests/differential.py`
proves it on one shared program.

`OptimizedProgram` (the CSE pass output, `repro.compiler.optimize`)
lowers through the same five backends: the executables run the
augmented shared-row bank and fold the shared partial sums back with
the program's combine matrix, so ``exe(x)`` still returns
``(out_filters, C, n_out)`` — bit-exact against lowering the parent.
The oracle reads ``effective_qbank()`` (weight-level reconstruction),
keeping it independent of the shared-row execution under test; the
vmachine widens ``coeff_bits`` because reduced/virtual row magnitudes
may exceed the parent's coefficient range.
"""
from __future__ import annotations

import numpy as np

from .program import BlmacProgram
from .schedule import DEFAULT_TILE, bank_plan

__all__ = ["BACKENDS", "Lowered", "lower"]

BACKENDS = ("oracle", "specialized", "scheduled", "vmachine", "sharded")


def _host_combine_i32(y: np.ndarray, combine: np.ndarray, n_real: int):
    """int32 shared-row fold on the host: int64 intermediate, then a
    wrapping cast — the same mod-2**32 residue as the in-kernel GEMM."""
    mixed = y[:n_real].astype(np.int64) + np.tensordot(
        combine, y[n_real:].astype(np.int64), axes=1
    )
    return mixed.astype(np.int32)


class Lowered:
    """An executable lowered from a `BlmacProgram` for one backend.

    Callable ``exe(x) -> np.ndarray (B, C, n_out)``; backend-specific
    handles (``.vmachine``, ``.fits``, ``.engine``) are attached as
    attributes where the backend has them.
    """

    def __init__(self, fn, backend: str, program: BlmacProgram, **extras):
        self._fn = fn
        self.backend = backend
        self.program = program
        for name, value in extras.items():
            setattr(self, name, value)

    def __call__(self, x) -> np.ndarray:
        return self._fn(x)

    def __repr__(self) -> str:
        return f"Lowered({self.backend}, {self.program!r})"


def _as_channels(x) -> np.ndarray:
    x = np.asarray(x)
    return x[None, :] if x.ndim == 1 else x


def lower(
    program: BlmacProgram,
    backend: str = "scheduled",
    *,
    channels: int = 1,
    tile: int | None = None,
    bank_tile: int | None = None,
    merge: int | None = None,
    interpret: bool | None = None,
    machine_spec=None,
    mesh=None,
) -> Lowered:
    """Lower ``program`` to an executable for ``backend`` (see module doc).

    Parameters
    ----------
    program : BlmacProgram
        The compiled artifact (from `compile_bank` / `compile_packed` /
        `BlmacProgram.load`).
    backend : str
        One of `BACKENDS`.
    channels, mesh
        Configure the sharded engine (the other backends infer C from
        the input).
    tile, bank_tile, merge
        Pin kernel geometry (None = `bank_plan`'s).
    interpret : bool | None
        Pallas interpret override for the kernel backends.
    machine_spec : repro.core.MachineSpec | None
        The vmachine's spec (default: the paper's parameters at this
        tap count).

    Returns
    -------
    Lowered
        Callable ``exe(x) -> (B, C, n_out)`` with backend-specific
        attributes (``.schedule``, ``.vmachine``, ``.fits``, ``.engine``).

    Raises
    ------
    TypeError
        ``program`` is not a `BlmacProgram`.
    ValueError
        Unknown ``backend``.

    Examples
    --------
    >>> import numpy as np
    >>> from repro.compiler import compile_bank, lower
    >>> bank = np.zeros((2, 15), np.int64)
    >>> bank[:, 7] = [64, 96]
    >>> prog = compile_bank(bank)
    >>> x = np.arange(30, dtype=np.int64)
    >>> y_oracle = lower(prog, "oracle")(x)
    >>> y_kernel = lower(prog, "scheduled", interpret=True)(x)
    >>> bool((y_oracle == y_kernel).all())       # bit-exact across backends
    True
    """
    if not isinstance(program, BlmacProgram):
        raise TypeError("lower() needs a BlmacProgram — call compile_bank")
    combine = program.combine  # None on plain programs
    n_real = program.out_filters if combine is not None else None
    if backend == "oracle":
        from ..filters.apply import fir_bit_layers_batch

        qbank = (
            program.qbank if combine is None else program.effective_qbank()
        )

        def run_oracle(x):
            return fir_bit_layers_batch(_as_channels(x), qbank)

        return Lowered(run_oracle, backend, program)

    if backend == "specialized":
        import jax.numpy as jnp

        from ..kernels.blmac_fir import blmac_fir_specialized

        pulses = program.pulse_schedules()
        taps = program.taps
        tile = tile or DEFAULT_TILE

        def run_specialized(x):
            xi = jnp.asarray(_as_channels(x), jnp.int32)
            n_out = xi.shape[-1] - taps + 1
            y = np.stack([
                np.stack([
                    np.asarray(
                        blmac_fir_specialized(xi[c], p, taps, tile, interpret)
                    )[:n_out]
                    for c in range(xi.shape[0])
                ])
                for p in pulses
            ])
            if combine is not None:
                y = _host_combine_i32(y, combine, n_real)
            return y

        return Lowered(run_specialized, backend, program)

    if backend == "scheduled":
        from ..kernels.blmac_fir import blmac_fir_bank

        plan, sched = bank_plan(program, tile, bank_tile, merge)

        def run_scheduled(x):
            return np.asarray(blmac_fir_bank(
                _as_channels(x), program.packed, program.taps, plan.tile,
                interpret=interpret, schedule=sched, fast_path=False,
                combine=combine, n_real=n_real,
            ))

        return Lowered(run_scheduled, backend, program, schedule=sched)

    if backend == "vmachine":
        import dataclasses

        from ..core.machine import MachineSpec
        from ..core.vmachine import FirBlmacVMachine

        spec = machine_spec or MachineSpec(taps=program.taps)
        if combine is not None:
            # reduced/virtual row magnitudes can exceed the parent's
            # coefficient range — widen, as machine_cycles() does
            spec = dataclasses.replace(
                spec, coeff_bits=max(spec.coeff_bits, program.n_layers + 1)
            )
        vm = FirBlmacVMachine(spec)
        fits = vm.program_bank(program.qbank)

        def run_vmachine(x):
            x2 = _as_channels(x)
            y = np.stack(
                [vm.run(x2[c]).outputs for c in range(x2.shape[0])], axis=1
            )
            if combine is not None:
                # the vmachine is exact int64: shared rows fold without
                # wrap, landing on the parent's exact outputs
                y = y[:n_real] + np.tensordot(combine, y[n_real:], axes=1)
            return y

        return Lowered(run_vmachine, backend, program, vmachine=vm, fits=fits)

    if backend == "sharded":
        from ..filters.sharded import ShardedFilterBankEngine

        # the sharded engine partitions rows across the bank mesh; an
        # optimized program shards its augmented bank (shared rows are
        # rows like any other) and folds after the gather
        eng = ShardedFilterBankEngine(
            program.bank if combine is not None else program,
            channels=channels, mesh=mesh, tile=tile, merge=merge,
            interpret=interpret,
        )

        def run_sharded(x):
            eng.reset()
            y = eng.push(_as_channels(x))
            if combine is not None:
                y = _host_combine_i32(np.asarray(y), combine, n_real)
            return y

        return Lowered(run_sharded, backend, program, engine=eng)

    raise ValueError(f"unknown backend {backend!r}; expected one of {BACKENDS}")
