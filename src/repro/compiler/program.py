"""`compile_bank(coeffs, spec) -> BlmacProgram`: filter compilation as a
first-class, cached, serializable step.

The paper's core object is a *compiled filter*: quantized taps → CSD bit
layers → a pulse/superlayer schedule a tiny machine executes.  PRs 1–4
re-derived that object at five call sites (numpy oracle, pulse-specialized
kernel, scheduled bank kernel, vmachine, sharded engine); `BlmacProgram`
computes it exactly once and every backend reads it off the artifact:

  * quantized coefficients (float input is quantized the paper's way,
    §3.2 power-of-two scaling; int input is taken as already quantized),
  * signed CSD digits and the packed 2-bit trit words
    (`pack_bank_trits` layout — the kernel operand format),
  * per-filter layer occupancy, occupancy signatures and pulse counts,
  * memoized superlayer schedules (`plan_bank_schedule`) per
    ``(bank_tile, merge)``,
  * memoized §4 machine cycle predictions per `MachineSpec`,
  * memoized bank partitions (the sharded engine's plan hook).

Programs are content-addressed (`ProgramCache`): compiling the same bank
twice — from coefficients or from an identical packed operand — is a
digest plus a dict hit.  `save()`/`load()` (npz + JSON header) let a
serving process warm-start without recompiling.
"""
from __future__ import annotations

import hashlib
import json
from dataclasses import dataclass

import numpy as np

from ..core.io import atomic_write, check_format_header
from ..core.csd import (assert_int32_bound, csd_decode, csd_digits,
                        layer_occupancy, occupancy_signatures, pack_trits,
                        packed_pulse_counts, require_type1, unpack_trits)
from .cache import PROGRAM_CACHE, _bump
from .schedule import (BankSchedule, MERGE_DEFAULT, default_bank_tile,
                       plan_bank_schedule)

__all__ = [
    "CompileSpec",
    "BlmacProgram",
    "ProgramFormatError",
    "PROGRAM_FORMAT_VERSION",
    "compile_bank",
    "compile_packed",
    "pack_bank_trits",
]

TRITS_PER_WORD = 16

# bump whenever the on-disk layout changes incompatibly; `load` rejects
# files written by a different version instead of mis-parsing them
PROGRAM_FORMAT_VERSION = 1


class ProgramFormatError(ValueError):
    """A saved program file has the wrong version or is corrupted."""


@dataclass(frozen=True)
class CompileSpec:
    """Compilation parameters — part of the program's content address.

    ``coeff_bits`` is the §3.2 quantization width applied to FLOAT
    coefficient input (integer banks are taken as already quantized);
    ``sample_bits`` the input-sample width of the §2.1 int32 accumulator
    bound, asserted once at compile; ``n_layers`` overrides the CSD digit
    count (None = minimal for the bank's magnitude range).
    """

    coeff_bits: int = 16
    sample_bits: int = 8
    n_layers: int | None = None


def _bank_digits(qbank: np.ndarray, n_layers: int | None) -> np.ndarray:
    """(B, taps) symmetric ints → (B, M, L) CSD digits of the folded half.

    The ONE place bank CSD encoding happens (counted in
    `cache_stats()["counters"]["csd_packings"]`).
    """
    _bump("csd_packings")
    half = qbank.shape[-1] // 2
    return csd_digits(qbank[:, : half + 1], n_digits=n_layers)


def pack_bank_trits(
    qbank: np.ndarray,
    n_layers: int | None = None,
    sample_bits: int = 8,
) -> np.ndarray:
    """(B, taps) symmetric int coefficients → (B, n_layers, n_words) uint32
    packed trit words over the folded half-filter (M = taps//2 + 1 rows),
    layer-major so the kernel slices one layer per Horner step.

    The int32 accumulator bound (§2.1) is asserted HERE, once per pack —
    `blmac_fir_bank`, `blmac_fir_dynamic` and `FilterBankEngine` all
    consume packed operands and inherit the guarantee for ``sample_bits``
    inputs (default 8-bit, the paper's operating point).

    Prefer `compile_bank` for anything beyond a one-off pack: it caches
    the result (and everything derived from it) content-addressed.
    """
    qbank = np.asarray(qbank, np.int64)
    if qbank.ndim != 2:
        raise ValueError("qbank must be (n_filters, taps)")
    require_type1(qbank, "bank kernel")
    assert_int32_bound(qbank, sample_bits, "bank kernel")
    digits = _bank_digits(qbank, n_layers)  # (B, M, L)
    return pack_trits(np.swapaxes(digits, 1, 2))  # (B, L, n_words)


def _qbank_key(qbank: np.ndarray, spec: CompileSpec):
    return (
        "q", hashlib.sha256(np.ascontiguousarray(qbank)).digest(),
        qbank.shape, spec.sample_bits, spec.n_layers,
    )


def _packed_key(packed: np.ndarray, taps: int, sample_bits: int):
    # geometry is folded into the digest itself (not just the key tuple):
    # the digest doubles as `BlmacProgram.key`, and identical trit BYTES
    # can arise from different tap counts (zero-padded trailing slots of
    # the last word) — those must not collide in digest-keyed caches
    h = hashlib.sha256(np.ascontiguousarray(packed))
    h.update(repr((packed.shape, int(taps), int(sample_bits))).encode())
    return ("p", h.digest(), packed.shape, int(taps), int(sample_bits))


def _memo_put(memo: dict, key, value, cap: int) -> None:
    """Insert into a bounded FIFO memo (dicts preserve insertion order):
    derived artifacts hold compacted bank copies, so per-program memos
    stay small — an evicted geometry is simply re-planned on demand."""
    memo[key] = value
    while len(memo) > cap:
        del memo[next(iter(memo))]


# per-program memo bounds: schedules/subprograms embed packed-bank copies,
# so cap them instead of growing forever.
SCHEDULE_MEMO_MAX = 16
SUBPROGRAM_MEMO_MAX = 32


class BlmacProgram:
    """One compiled BLMAC filter bank — the artifact every backend executes.

    Read-only by contract (the arrays are flagged unwritable; programs are
    shared across engines, plans and caches).  Construct via
    `compile_bank` / `compile_packed` / `load`, never directly.

    Attributes
    ----------
    key : str
        Hex content digest of the packed trit operand — the program's
        content address (stable across ``save``/``load``).
    qbank : (B, taps) int64
        Quantized coefficients.
    exponents : (B,) int64
        Per-filter §3.2 power-of-two scale exponents (zero when compiled
        from already-quantized integers): float ≈ qbank · 2^−exponent.
    packed : (B, n_layers, n_words) uint32
        Packed 2-bit trit words over the folded half-filter — the bank
        kernel's weight-memory image.
    occupancy : (B, n_layers) bool;  signatures : (B,) uint64
        Which bit layers hold pulses, and the sort key that groups
        schedule-identical filters.
    pulse_counts : (B,) int64
        Non-zero trits per filter — the §3.3 add count less the folds.
    """

    # non-None only on `repro.compiler.optimize.OptimizedProgram`; plain
    # consumers can branch on `program.combine is not None` (or on
    # `parent`) without importing the optimize module
    combine = None
    parent = None

    def __init__(self, *, qbank, exponents, packed, occupancy, signatures,
                 pulse_counts, spec: CompileSpec, key: str):
        self.qbank = qbank
        self.exponents = exponents
        self.packed = packed
        self.occupancy = occupancy
        self.signatures = signatures
        self.pulse_counts = pulse_counts
        self.spec = spec
        self.key = key
        self.n_filters, self.taps = qbank.shape
        _, self.n_layers, self.n_words = packed.shape
        for a in (qbank, exponents, packed, occupancy, signatures,
                  pulse_counts):
            a.setflags(write=False)
        # memoized derived artifacts — the whole point of the program
        self._schedules: dict = {}
        self._cycle_cache: dict = {}
        self._partitions: dict = {}
        self._subprograms: dict = {}
        self._half_digits = None
        self._pulse_schedules = None

    def __repr__(self) -> str:
        return (
            f"BlmacProgram(B={self.n_filters}, taps={self.taps}, "
            f"layers={self.n_layers}, key={self.key[:12]}…)"
        )

    # -- derived views -------------------------------------------------------

    @property
    def out_filters(self) -> int:
        """Filters this program serves — equals ``n_filters`` here;
        an `OptimizedProgram` serves fewer than its row count (the
        extra rows are shared partial sums)."""
        return self.n_filters

    def total_adds(self) -> int:
        """§3.3 additions to produce one output sample of the whole
        bank: ``taps//2`` symmetric folds per filter plus one add per
        CSD pulse — the paper's adds-per-filter metric times B, and the
        baseline the CSE pass (`repro.compiler.optimize`) reduces."""
        return self.n_filters * (self.taps // 2) + int(
            self.pulse_counts.sum()
        )

    @property
    def filter_costs(self) -> np.ndarray:
        """(B,) float64 predicted per-filter work: pulses + symmetric
        folds — the quantity `partition_bank` balances (identical to
        `repro.distributed.sharding.bank_filter_costs`)."""
        return self.pulse_counts.astype(np.float64) + self.taps // 2

    def half_digits(self) -> np.ndarray:
        """(B, M, n_layers) int8 signed CSD digits of the folded half,
        LSB-first layers — unpacked from the trit words once, then shared
        (read-only)."""
        if self._half_digits is None:
            half = self.taps // 2
            d = unpack_trits(self.packed, half + 1)  # (B, L, M)
            d = np.ascontiguousarray(np.swapaxes(d, 1, 2))
            d.setflags(write=False)
            self._half_digits = d
        return self._half_digits

    def pulse_schedules(self) -> tuple:
        """Per-filter MSB-first static pulse tuples ``(layer, j, sign)`` —
        the `specialized_program` input, derived once from the digits."""
        if self._pulse_schedules is None:
            digits = self.half_digits()  # (B, M, L)
            out = []
            for b in range(self.n_filters):
                d = digits[b]
                pulses = []
                for layer in range(d.shape[1] - 1, -1, -1):
                    for j in np.nonzero(d[:, layer])[0]:
                        pulses.append((int(layer), int(j), int(d[j, layer])))
                out.append(tuple(pulses))
            self._pulse_schedules = tuple(out)
        return self._pulse_schedules

    def schedule(
        self, bank_tile: int | None = None, merge: int | None = None
    ) -> BankSchedule:
        """The memoized superlayer schedule for one kernel geometry.

        Engine construction (through `bank_plan`) and benchmarks all
        land here — one `plan_bank_schedule` per distinct
        ``(bank_tile, merge)`` per program, however many clients ask.
        """
        bt = default_bank_tile(self.n_filters) if bank_tile is None \
            else int(bank_tile)
        mg = MERGE_DEFAULT if merge is None else int(merge)
        key = (bt, mg)
        if key not in self._schedules:
            _memo_put(
                self._schedules, key,
                plan_bank_schedule(self.packed, bt, mg), SCHEDULE_MEMO_MAX,
            )
        return self._schedules[key]

    def machine_cycles(self, spec=None) -> np.ndarray:
        """(B,) §4 machine clock cycles per output sample, per filter.

        Derived from the program's OWN digits (no CSD recomputation):
        layers are sliced/padded to ``spec.n_layers`` — exact, because
        NAF digit values are independent of the requested width — and a
        bank whose digits populate layers the spec lacks raises, like
        `machine_cycles_batch` would.  Memoized per spec parameters;
        agrees bit-for-bit with both simulators (`tests/differential.py`).
        """
        from ..core.machine import MachineSpec
        from ..core.rle import code_count_batch

        if spec is None:
            spec = MachineSpec(taps=self.taps)
        if spec.taps != self.taps:
            raise ValueError(
                f"spec is for {spec.taps} taps, bank has {self.taps}"
            )
        key = (spec.n_layers, spec.start_overhead, spec.fused_last_add)
        if key not in self._cycle_cache:
            _bump("machine_cycle_computes")
            digits = self.half_digits()  # (B, M, L) LSB-first
            n = int(spec.n_layers)
            if digits.shape[-1] > n:
                if self.occupancy[:, n:].any():
                    raise ValueError(
                        f"bank populates CSD layer >= {n}; spec has only "
                        f"{n} layers"
                    )
                digits = digits[..., :n]
            elif digits.shape[-1] < n:
                pad = np.zeros(
                    digits.shape[:-1] + (n - digits.shape[-1],), np.int8
                )
                digits = np.concatenate([digits, pad], axis=-1)
            cycles = code_count_batch(digits) + spec.start_overhead
            if spec.fused_last_add:
                cycles = cycles - np.count_nonzero(
                    digits.any(axis=1), axis=-1
                )
            cycles.setflags(write=False)  # shared cache entry: no mutation
            self._cycle_cache[key] = cycles
        return self._cycle_cache[key]

    def partition(self, n_shards: int):
        """Memoized occupancy-balanced `BankPartition` over ``n_shards``
        (the hook `repro.distributed.sharding.sharded_plan` plans with).

        Parameters
        ----------
        n_shards : int
            Number of contiguous (post-occupancy-sort) filter shards.

        Returns
        -------
        repro.distributed.sharding.BankPartition
            ``.assign`` lists each shard's original filter indices;
            ``.imbalance`` is max/mean predicted shard cost.

        Raises
        ------
        ValueError
            ``n_shards < 1`` or more shards than filters.

        Examples
        --------
        >>> import numpy as np
        >>> from repro.compiler import compile_bank
        >>> bank = np.zeros((4, 15), np.int64)
        >>> bank[:, 7] = [64, 96, 160, 224]
        >>> part = compile_bank(bank).partition(2)
        >>> sorted(len(rows) for rows in part.assign)
        [2, 2]
        """
        from ..distributed.sharding import partition_bank

        n_shards = int(n_shards)
        if n_shards not in self._partitions:
            self._partitions[n_shards] = partition_bank(
                self.packed, n_shards, self.taps,
                cost=self.filter_costs, sig=self.signatures,
            )
        return self._partitions[n_shards]

    def select(self, rows) -> "BlmacProgram":
        """The subprogram serving ``rows`` (original filter indices, in
        order) — array slices of this program, no recompilation.  Memoized
        here AND registered content-addressed, so every sharded plan and
        engine asking for the same shard gets one object.

        Parameters
        ----------
        rows : sequence of int
            Original filter indices, in the order the subprogram should
            serve them.

        Returns
        -------
        BlmacProgram
            The sliced program (same taps/spec, ``len(rows)`` filters).

        Raises
        ------
        IndexError
            A row index is out of range for this bank.

        Examples
        --------
        >>> import numpy as np
        >>> from repro.compiler import compile_bank
        >>> bank = np.zeros((3, 15), np.int64)
        >>> bank[:, 7] = [64, 96, 160]
        >>> prog = compile_bank(bank)
        >>> sub = prog.select([2, 0])
        >>> sub.n_filters, [int(w) for w in sub.qbank[:, 7]]
        (2, [160, 64])
        >>> prog.select([2, 0]) is sub               # memoized
        True
        """
        rows = np.asarray(rows, np.int64)
        memo = rows.tobytes()
        if memo in self._subprograms:
            return self._subprograms[memo]
        qbank = np.ascontiguousarray(self.qbank[rows])
        packed = np.ascontiguousarray(self.packed[rows])
        qkey = _qbank_key(qbank, self.spec)
        pkey = _packed_key(packed, self.taps, self.spec.sample_bits)
        sub = PROGRAM_CACHE.get(pkey)
        if sub is None:
            sub = BlmacProgram(
                qbank=qbank,
                exponents=np.ascontiguousarray(self.exponents[rows]),
                packed=packed,
                occupancy=np.ascontiguousarray(self.occupancy[rows]),
                signatures=np.ascontiguousarray(self.signatures[rows]),
                pulse_counts=np.ascontiguousarray(self.pulse_counts[rows]),
                spec=self.spec,
                key=pkey[1].hex(),
            )
            PROGRAM_CACHE.put(sub, pkey, qkey)
        _memo_put(self._subprograms, memo, sub, SUBPROGRAM_MEMO_MAX)
        return sub

    # -- serialization -------------------------------------------------------

    def save(self, path) -> None:
        """Write the program to ``path``: one npz holding the arrays plus
        a JSON header (format version, geometry, content key) — a serving
        process `load`s it and warm-starts without recompiling.  The
        write is atomic (tmp file + rename): a killed process leaves the
        previous file intact, never a truncated one.

        Parameters
        ----------
        path : str | os.PathLike
            Destination file (conventionally ``*.npz``); parent
            directory must exist.

        Raises
        ------
        OSError
            The destination is not writable.

        Examples
        --------
        >>> import numpy as np, os, tempfile
        >>> from repro.compiler import BlmacProgram, compile_bank
        >>> bank = np.zeros((2, 15), np.int64)
        >>> bank[:, 7] = [64, 96]
        >>> prog = compile_bank(bank)
        >>> path = os.path.join(tempfile.mkdtemp(), "bank.npz")
        >>> prog.save(path)
        >>> BlmacProgram.load(path) is prog      # content-addressed hit
        True
        """
        header = {
            "format_version": PROGRAM_FORMAT_VERSION,
            "kind": "blmac_program",
            "key": self.key,
            "n_filters": self.n_filters,
            "taps": self.taps,
            "n_layers": self.n_layers,
            "n_words": self.n_words,
            "spec": {
                "coeff_bits": self.spec.coeff_bits,
                "sample_bits": self.spec.sample_bits,
                "n_layers": self.spec.n_layers,
            },
        }
        atomic_write(path, lambda f: np.savez(
            f,
            header=np.array(json.dumps(header)),
            qbank=self.qbank,
            exponents=self.exponents,
            packed=self.packed,
        ))

    @classmethod
    def load(cls, path) -> "BlmacProgram":
        """Read a program written by `save`.

        Every way the file can be bad raises `ProgramFormatError`: a
        different format version, an unreadable/truncated archive, a
        header digest that does not match the packed trits, or stored
        coefficients that do not decode from the trits (the case where
        the oracle backend and the kernels would silently diverge).
        Callers can therefore `except ProgramFormatError` and fall back
        to recompiling.  The loaded program is registered content-
        addressed, so later `compile_bank` calls for the same bank hit
        it instead of recompiling.

        Parameters
        ----------
        path : str | os.PathLike
            A file written by `save`.

        Returns
        -------
        BlmacProgram
            The loaded (or cache-hit) program.

        Raises
        ------
        ProgramFormatError
            Wrong version, unreadable archive, digest mismatch, or
            coefficients that do not decode from the stored trits.

        Examples
        --------
        >>> from repro.compiler import BlmacProgram, ProgramFormatError
        >>> try:
        ...     BlmacProgram.load("/nonexistent/bank.npz")
        ... except ProgramFormatError:
        ...     print("fall back to compile_bank")
        fall back to compile_bank
        """
        try:
            with np.load(path, allow_pickle=False) as z:
                header = json.loads(str(z["header"][()]))
                check_format_header(
                    header, kind="blmac_program",
                    version=PROGRAM_FORMAT_VERSION, path=path,
                    error_cls=ProgramFormatError, label="BLMAC program",
                )
                qbank = np.ascontiguousarray(z["qbank"], np.int64)
                exponents = np.ascontiguousarray(z["exponents"], np.int64)
                packed = np.ascontiguousarray(z["packed"], np.uint32)
                combine = use_counts = None
                if "cse" in header:  # an optimized program (see optimize.py)
                    combine = np.asarray(z["combine"], np.int64)
                    use_counts = np.asarray(z["use_counts"], np.int64)
        except ProgramFormatError:
            raise
        except Exception as e:  # truncated zip, missing array, bad JSON …
            raise ProgramFormatError(f"{path}: unreadable program file: {e}")
        spec = CompileSpec(**header["spec"])
        taps = int(header["taps"])
        pkey = _packed_key(packed, taps, spec.sample_bits)
        # an optimized file's `key` is its CSE content address; the raw
        # trit digest moves to `packed_digest` (same integrity check)
        if pkey[1].hex() != header.get("packed_digest", header.get("key")):
            raise ProgramFormatError(
                f"{path}: content digest mismatch (corrupted file?)"
            )
        # the digest covers the packed trits; cross-check the stored
        # coefficients against them so a corrupted qbank cannot make the
        # oracle backend diverge from the kernels
        half = taps // 2
        halves = csd_decode(np.swapaxes(unpack_trits(packed, half + 1), 1, 2))
        if not np.array_equal(
            qbank, np.concatenate([halves, halves[:, :-1][:, ::-1]], axis=1)
        ):
            raise ProgramFormatError(
                f"{path}: stored coefficients do not decode from the packed "
                f"trits — digest mismatch (corrupted file?)"
            )
        if "cse" in header:
            from .optimize import _load_optimized

            return _load_optimized(
                path, header, qbank, exponents, packed, combine, use_counts
            )
        cached = PROGRAM_CACHE.get(pkey)
        if cached is not None:
            return cached
        prog = _from_arrays(qbank, exponents, packed, spec)
        PROGRAM_CACHE.put(prog, pkey, _qbank_key(qbank, spec))
        return prog


def _from_arrays(
    qbank: np.ndarray,
    exponents: np.ndarray,
    packed: np.ndarray,
    spec: CompileSpec,
) -> BlmacProgram:
    """Assemble a program from its stored arrays — derives only the cheap
    views (occupancy, signatures, pulse counts read off the packed words),
    never re-runs CSD encoding."""
    taps = qbank.shape[-1]
    require_type1(qbank, "compile_bank")
    assert_int32_bound(qbank, spec.sample_bits, "compile_bank")
    occupancy = np.ascontiguousarray(packed.any(axis=-1))
    signatures = occupancy_signatures(occupancy)
    pulse_counts = packed_pulse_counts(packed)
    return BlmacProgram(
        qbank=qbank,
        exponents=np.ascontiguousarray(exponents),
        packed=packed,
        occupancy=occupancy,
        signatures=np.ascontiguousarray(signatures),
        pulse_counts=pulse_counts,
        spec=spec,
        key=_packed_key(packed, taps, spec.sample_bits)[1].hex(),
    )


def compile_bank(coeffs, spec: CompileSpec | None = None) -> BlmacProgram:
    """Compile a filter bank to a `BlmacProgram` — THE entry point of the
    one-program/five-backends pipeline.

    Content-addressed: the same bank compiles once per process (then per
    `save` file across processes) — every engine and
    predictor shares the artifact and its memoized schedules, partitions
    and cycle predictions.

    Parameters
    ----------
    coeffs : (B, taps) or (taps,) array
        Odd symmetric type-I coefficients.  Float input is quantized
        per-row the paper's way (§3.2, `po2_quantize_batch` at
        ``spec.coeff_bits``); integer input is taken as already
        quantized.
    spec : CompileSpec | None
        Compilation parameters (quantization width, sample bits, CSD
        layer count); part of the content address.

    Returns
    -------
    BlmacProgram
        The compiled (or cache-hit) program.

    Raises
    ------
    ValueError
        Coefficients are not type-I (even tap count / asymmetric), or
        the §2.1 int32 accumulator bound fails at ``spec.sample_bits``.
    TypeError
        Coefficient dtype is neither float nor integer.

    Examples
    --------
    >>> import numpy as np
    >>> from repro.compiler import compile_bank
    >>> bank = np.zeros((2, 15), np.int64)
    >>> bank[:, 7] = [64, 96]                    # centre-tap scalers
    >>> prog = compile_bank(bank)
    >>> prog.n_filters, prog.taps
    (2, 15)
    >>> compile_bank(bank) is prog               # content-addressed
    True
    """
    spec = spec or CompileSpec()
    coeffs = np.atleast_2d(np.asarray(coeffs))
    if coeffs.ndim != 2:
        raise ValueError("coeffs must be (n_filters, taps)")
    if coeffs.dtype.kind == "f":
        from ..core.quantize import po2_quantize_batch

        qbank, exponents = po2_quantize_batch(coeffs, spec.coeff_bits)
        exponents = np.ascontiguousarray(exponents, np.int64)
    elif coeffs.dtype.kind in "iu":
        qbank = coeffs.astype(np.int64)
        exponents = np.zeros(qbank.shape[0], np.int64)
    else:
        raise TypeError(f"cannot compile coefficients of dtype {coeffs.dtype}")
    qbank = np.ascontiguousarray(qbank)
    qkey = _qbank_key(qbank, spec)
    prog = PROGRAM_CACHE.get(qkey)
    if prog is not None:
        return prog
    require_type1(qbank, "compile_bank")
    assert_int32_bound(qbank, spec.sample_bits, "compile_bank")
    digits = _bank_digits(qbank, spec.n_layers)  # (B, M, L) — ONCE
    packed = pack_trits(np.swapaxes(digits, 1, 2))  # (B, L, n_words)
    pkey = _packed_key(packed, qbank.shape[-1], spec.sample_bits)
    # a bank first seen through `compile_packed` (or a shard `select`) is
    # registered under its packed digest only — adopt that program rather
    # than building a duplicate, and index it under this qbank key too
    existing = PROGRAM_CACHE.get(pkey)
    if existing is not None:
        PROGRAM_CACHE.put(existing, qkey)
        return existing
    _bump("bank_compiles")
    occupancy = np.ascontiguousarray(layer_occupancy(digits))
    prog = BlmacProgram(
        qbank=qbank,
        exponents=exponents,
        packed=packed,
        occupancy=occupancy,
        signatures=np.ascontiguousarray(occupancy_signatures(occupancy)),
        pulse_counts=np.count_nonzero(digits, axis=(1, 2)).astype(np.int64),
        spec=spec,
        key=pkey[1].hex(),
    )
    # digits were just computed — seed the memo instead of re-unpacking
    prog._half_digits = np.ascontiguousarray(digits)
    prog._half_digits.setflags(write=False)
    PROGRAM_CACHE.put(prog, qkey, pkey)
    return prog


def compile_packed(
    packed: np.ndarray, taps: int, sample_bits: int = 8
) -> BlmacProgram:
    """Wrap an existing packed-trit operand (`pack_bank_trits` output) as
    a `BlmacProgram` WITHOUT re-running CSD encoding: the quantized
    coefficients are decoded from the trits (exact — the trit words ARE
    the weights).  Content-addressed like `compile_bank`; a bank packed
    and a bank compiled from the same coefficients at the same layer
    count resolve to one program."""
    packed = np.ascontiguousarray(np.asarray(packed, np.uint32))
    if packed.ndim != 3:
        raise ValueError("packed must be (n_filters, n_layers, n_words)")
    pkey = _packed_key(packed, int(taps), sample_bits)
    prog = PROGRAM_CACHE.get(pkey)
    if prog is not None:
        return prog
    _bump("bank_compiles")
    # a program owns (and freezes) its arrays; copy rather than adopt the
    # caller's buffer — freezing it would be a visible side effect, and a
    # writable alias could mutate cached content under a stale digest
    packed = packed.copy()
    half = int(taps) // 2
    digits = unpack_trits(packed, half + 1)  # (B, L, M)
    halves = csd_decode(np.swapaxes(digits, 1, 2))  # (B, M)
    qbank = np.ascontiguousarray(
        np.concatenate([halves, halves[:, :-1][:, ::-1]], axis=1)
    )
    spec = CompileSpec(sample_bits=sample_bits, n_layers=packed.shape[1])
    prog = _from_arrays(
        qbank, np.zeros(qbank.shape[0], np.int64), packed, spec
    )
    PROGRAM_CACHE.put(prog, pkey, _qbank_key(qbank, spec))
    return prog
