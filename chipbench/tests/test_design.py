"""The benchmark's own bank design, pinned by checksums of its quantized
coefficients.  The checksums were taken from this design; the program's
own design (`repro.filters.sweep_bank` + `po2_quantize_batch`) gave the
same integers when they were compared, but no test here imports it, so
a change to the program cannot move the benchmark."""
import json
import pathlib

import numpy as np
import pytest

from chipbench import design

CONFIGS = pathlib.Path(__file__).resolve().parents[1] / "configs"

CHECKSUMS = {
    "fir127_grid": ((9900, 127), "f404c8dab94ddfd154a9738021c418706d52b09a"
                                 "375cc088861d577a2af9244f"),
    "fir255_grid": ((9900, 255), "29a63e40d1b3c1687150ebb4e58b4cd886dfd34a"
                                 "4e75b9e663f099b5e5178d0b"),
}


def _cfg(name):
    with open(CONFIGS / f"{name}.json") as f:
        return json.load(f)


@pytest.mark.parametrize("name", sorted(CHECKSUMS))
def test_design_matches_its_checksum(name):
    shape, digest = CHECKSUMS[name]
    q = design.design(_cfg(name))
    assert q.shape == shape
    assert np.abs(q).max() < 2 ** 15
    assert design.checksum(q) == digest


def test_grid_holds_the_four_kinds_in_the_papers_counts():
    bands = design.grid_bands(100)
    assert len(bands) == 9900
    low = [b for b in bands if len(b) == 1 and b[0, 0] == 0.0]
    high = [b for b in bands if len(b) == 1 and b[0, 1] == 1.0]
    stop = [b for b in bands if len(b) == 2]
    assert (len(low), len(high), len(stop)) == (99, 99, 4851)
    assert len(bands) - len(low) - len(high) - len(stop) == 4851


def test_library_takes_every_kind_at_even_intervals():
    rows = design.select_rows(9900, {"select": "even", "n_filters": 256})
    assert len(rows) == 256 and len(set(rows.tolist())) == 256
    assert rows.min() < 99 < rows[rows >= 99].min() < 198  # low, high
    assert (rows >= 198).sum() > 100 and (rows >= 198 + 4851).sum() > 100


def test_filters_are_type_one():
    q = design.design(_cfg("fir255_grid"))
    np.testing.assert_array_equal(q, q[:, ::-1])


@pytest.mark.parametrize("window", sorted(design.WINDOWS))
def test_every_window_designs_type_one_filters_at_unit_gain(window):
    cfg = {"name": "w", "taps": 63, "n_div": 5, "window": window,
           "select": "all", "n_filters": 20, "coeff_bits": 16}
    h = design.windowed_sinc(63, design.grid_bands(5), window)
    np.testing.assert_allclose(h, h[:, ::-1])
    np.testing.assert_allclose(h[:4].sum(axis=1), 1.0)  # low-pass DC gain
    assert design.design(cfg).shape == (20, 63)


def test_design_refuses_an_unknown_window():
    with pytest.raises(ValueError, match="kaiser"):
        design.windowed_sinc(31, design.grid_bands(5), "kaiser")


def test_load_bank_saves_once_and_reads_back(tmp_path):
    cfg = {"name": "small", "taps": 31, "n_div": 5, "window": "hamming",
           "select": "all", "n_filters": 20, "coeff_bits": 16}
    q = design.load_bank(cfg, tmp_path)
    files = list(tmp_path.iterdir())
    assert len(files) == 1
    np.testing.assert_array_equal(design.load_bank(cfg, tmp_path), q)
    other = dict(cfg, taps=33)
    design.load_bank(other, tmp_path)
    assert len(list(tmp_path.iterdir())) == 2  # keyed by the design


def test_design_refuses_a_count_it_cannot_make():
    cfg = {"name": "x", "taps": 31, "n_div": 5, "window": "hamming",
           "select": "all", "n_filters": 7, "coeff_bits": 16}
    with pytest.raises(ValueError):
        design.design(cfg)
