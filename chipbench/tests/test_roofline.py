"""The work function behind ``device_roofline.stream`` and the table of
peaks."""
import json

import pytest

from chipbench import roofline

V5E = "TPU v5 lite"


def test_peaks_are_the_published_v5e_numbers():
    p = roofline.peaks(V5E)
    assert p == {"bf16_flops": 1.97e14, "int8_ops": 3.93e14,
                 "hbm_bytes_per_s": 8.19e11, "hbm_bytes": 1.6e10}


def test_a_device_missing_from_the_table_is_an_error():
    with pytest.raises(KeyError, match="cpu"):
        roofline.peaks("cpu")


def test_peaks_table_names_its_source():
    with open(roofline.PEAKS) as f:
        assert "TPU v5e" in json.load(f)["source"]


@pytest.mark.parametrize("b, c, n_out, taps, ops, nbytes", [
    # fir127_grid.sweep: 9,900 filters, 1 channel, 4,096-sample pushes
    (9900, 1, 4096, 127, 2 * 9900 * 4096 * 64,
     4 * 9900 * 4096 + 4096 + 9900 * 127 * 2),
    # fir255_grid.sweep: the same at 255 taps
    (9900, 1, 4096, 255, 2 * 9900 * 4096 * 128,
     4 * 9900 * 4096 + 4096 + 9900 * 255 * 2),
])
def test_push_work_at_both_configurations(b, c, n_out, taps, ops, nbytes):
    got_ops, got_bytes = roofline.push_work(b, c, n_out, taps)
    assert got_ops == ops and got_bytes == nbytes
    p = roofline.peaks(V5E)
    least = roofline.least_seconds(got_ops, got_bytes, p)
    # memory-bound at both tap counts: the bytes set the least time
    assert least == got_bytes / p["hbm_bytes_per_s"]
    assert got_ops / p["int8_ops"] < least
