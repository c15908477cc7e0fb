"""The peaks table and the bytes a sort must move."""

import json

import pytest

from chipbench import roofline


def test_v5e_peaks_from_the_table():
    p = roofline.load_peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["peak_bf16_flops"] == 197e12
    assert p["hbm_bytes"] == 16e9
    assert "TPU v5e" in p["source"]


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v5", ""])
def test_unknown_device_is_an_error(kind):
    with pytest.raises(roofline.UnknownDevice):
        roofline.load_peaks(kind)


def test_peaks_table_names_a_source_for_every_device():
    table = json.loads(roofline.PEAKS_FILE.read_text())
    assert table and all(entry["source"] for entry in table.values())


@pytest.mark.parametrize("n,itemsize,want", [
    (15728640, 4, 125829120),  # the 60 MB array: 60 MiB read, 60 MiB written
    (1, 4, 8),
    (4096, 8, 65536),
    (0, 4, 0),
])
def test_sort_bytes_read_once_write_once(n, itemsize, want):
    assert roofline.sort_bytes(n, itemsize) == want


def test_roofline_share():
    peaks = {"hbm_bytes_per_s": 819e9}
    # 819 MB at 819 GB/s is 1 ms: 1 ms of device time is the whole roofline
    assert roofline.hbm_roofline_pct(819e6, 1e-3, peaks) == pytest.approx(100.0)
    assert roofline.hbm_roofline_pct(819e6, 4e-3, peaks) == pytest.approx(25.0)
    # the 60 MB sort at ~0.97 s of device time
    share = roofline.hbm_roofline_pct(roofline.sort_bytes(15728640, 4), 0.97, peaks)
    assert share == pytest.approx(0.01584, rel=1e-3)


@pytest.mark.parametrize("n_bytes,seconds", [(0, 1.0), (100, 0.0), (100, -1.0)])
def test_no_share_without_time_or_bytes(n_bytes, seconds):
    assert roofline.hbm_roofline_pct(n_bytes, seconds, {"hbm_bytes_per_s": 1.0}) is None
