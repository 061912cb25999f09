"""The filter-bank kernels compile for a TPU v5e, without the chip.

The TPU compiler is installed with jax; it compiles for a described
``v5e:2x2`` topology and refuses here what the chip's compiler would
refuse (block layouts, in-kernel gathers, operand dtypes, memory).  The
geometry is the one `chip_smoke.py` runs: the paper's 127-tap
configuration, a 256-filter bank over 8 channels of 16,384-sample
chunks, and the 9,900-filter §3.1 sweep on one channel.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this
file.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.compiler import compile_bank
from repro.configs.fir127 import CONFIG
from repro.core import po2_quantize_batch
from repro.distributed import halo_exchange_left
from repro.filters import FilterBankEngine, spread_lowpass_qbank, sweep_bank
from repro.kernels.blmac_fir import (_bank_call, _bank_call_xla,
                                     bank_schedule_apply, frame_signal_batch,
                                     plane_combine, specialized_program)

TAPS = CONFIG.taps
CHUNK = 16384


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # entries compiled for a described chip cannot be read back here
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _steady_frames_shape(n_chan: int, tile: int):
    """(C, n_tiles, frame_len) of a steady `push` of CHUNK samples: the
    engine frames ``taps − 1`` tail samples + the chunk, padded to a
    tile multiple."""
    n = -(-(CHUNK + TAPS - 1) // tile) * tile
    frames, _ = jax.eval_shape(
        lambda x: frame_signal_batch(x, TAPS, tile),
        jax.ShapeDtypeStruct((n_chan, n), jnp.int32),
    )
    return frames.shape


def _engine(qbank, channels):
    """The engine's own plan (autotuned here on the CPU, as on the chip:
    the default sweep is backend-independent)."""
    return FilterBankEngine(compile_bank(qbank), channels=channels)


def _compile_groups(eng, sharding):
    shape = _steady_frames_shape(eng.channels, eng.tile)
    frames = jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)
    texts = []
    for g in eng.bank_schedule.groups:
        op = jax.ShapeDtypeStruct(g.packed.shape, jnp.int32, sharding=sharding)
        texts.append(_bank_call.lower(
            frames, op, taps=TAPS, schedule=g.schedule,
            tail_shift=g.tail_shift, tile=eng.tile,
            bank_tile=eng.bank_schedule.tile_size, interpret=False,
        ).compile().as_text())
    return texts


@pytest.mark.parametrize("bank", ["stream", "sweep"])
def test_mosaic_bank_kernel_compiles(bank, one_chip):
    """Every tile group of the default engine's schedule compiles to a
    Mosaic kernel: the 256-filter stream bank (8 channels) and all
    occupancy groups of the 9,900-filter sweep (1 channel)."""
    if bank == "stream":
        eng = _engine(spread_lowpass_qbank(256, TAPS), 8)
    else:
        q, _ = po2_quantize_batch(
            sweep_bank(TAPS, CONFIG.n_div, CONFIG.window), CONFIG.coeff_bits
        )
        eng = _engine(q, 1)
    texts = _compile_groups(eng, one_chip)
    assert texts and all("tpu_custom_call" in t for t in texts)


@pytest.mark.parametrize("n_out", [4096, 3970])
def test_plane_combine_kernel_compiles(n_out, one_chip):
    """The 16-bit path's combine kernel at the I/Q sweep's shapes: the
    9,900-filter bank's two planes of two channels, steady pushes and
    the first push (a partial last block of columns).  The result is
    laid out row-major, so its host copy reads as int64 words."""
    y = jax.ShapeDtypeStruct((9900, 4, 4352), jnp.int32, sharding=one_chip)
    compiled = plane_combine.lower(y, n_out, False).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "blmac_plane_combine" in text
    assert compiled.output_formats.layout.major_to_minor == (0, 1, 2)


def test_specialized_program_compiles(one_chip):
    """The pulse-baked single-filter kernel (banks of <= 32 filters and
    the B = 1 fast path) compiles for one 127-tap filter."""
    prog = compile_bank(spread_lowpass_qbank(1, TAPS))
    run = specialized_program(prog.pulse_schedules()[0], TAPS, 512, False)
    x = jax.ShapeDtypeStruct((CHUNK,), jnp.int32, sharding=one_chip)
    assert "tpu_custom_call" in run.lower(x).compile().as_text()


def test_xla_bank_call_compiles_within_device_memory(one_chip):
    """The fused XLA lowering of the same schedule compiles, and its
    window matrix and output fit one chip's 16 GB."""
    eng = _engine(spread_lowpass_qbank(256, TAPS), 8)
    g = eng.bank_schedule.groups[0]
    frames = jax.ShapeDtypeStruct(
        _steady_frames_shape(8, eng.tile), jnp.int32, sharding=one_chip
    )
    op = jax.ShapeDtypeStruct(g.packed.shape, jnp.int32, sharding=one_chip)
    mem = _bank_call_xla.lower(
        frames, op, taps=TAPS, schedule=g.schedule, tail_shift=g.tail_shift,
        tile=eng.tile,
    ).compile().memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert 0 < used < 16 * 10**9


def test_time_sharded_step_compiles_on_four_chips(topo):
    """The data axis in "time" mode over all four chips: a halo exchange
    (one ppermute of taps − 1 samples) feeding the Mosaic bank kernel
    inside shard_map, as `ShardedFilterBankEngine` runs it."""
    eng = _engine(spread_lowpass_qbank(256, TAPS), 1)
    sched, tile = eng.bank_schedule, eng.tile
    mesh = Mesh(np.asarray(topo.devices), ("data",))

    def step(buf, *ops):
        xl = halo_exchange_left(buf, "data", 4, TAPS - 1)
        frames, _ = frame_signal_batch(xl, TAPS, tile)
        y = bank_schedule_apply(frames, sched, TAPS, tile, False,
                                device_groups=list(ops))
        return y[:, :, :buf.shape[-1]]

    mapped = jax.jit(jax.shard_map(
        step, mesh=mesh,
        in_specs=(P(None, "data"),) + (P(),) * len(sched.groups),
        out_specs=P(None, None, "data"), check_vma=False,
    ))
    x = jax.ShapeDtypeStruct((1, 4 * 4096), jnp.int32,
                             sharding=NamedSharding(mesh, P(None, "data")))
    ops = [jax.ShapeDtypeStruct(g.packed.shape, jnp.int32,
                                sharding=NamedSharding(mesh, P()))
           for g in sched.groups]
    text = mapped.lower(x, *ops).compile().as_text()
    assert "tpu_custom_call" in text and "collective-permute" in text
