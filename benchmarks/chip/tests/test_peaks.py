"""The peaks table is keyed by device kind; an unknown kind is an error."""
import pytest

import peaks


def test_v5e_peaks():
    p = peaks.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert p["int8_ops"] == 393e12 and p["hbm_bytes"] == 16e9


def test_unknown_kind_is_an_error():
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks("cpu")
