"""The peaks table."""
import json

import pytest

from bench import peaks


def test_peaks_table_v5e_and_unknown_kind(tmp_path):
    p = peaks.peaks_for("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["bf16_flops_per_s"] == 197e12
    assert p["int8_ops_per_s"] == 393e12
    with pytest.raises(KeyError, match="no peaks"):
        peaks.peaks_for("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")
    f = tmp_path / "peaks.json"
    f.write_text(json.dumps({"devices": {"X": {"hbm_bytes_per_s": 1.0}}}))
    assert peaks.peaks_for("X", str(f)) == {"hbm_bytes_per_s": 1.0}
