"""The fused kernel's work count and roofline share, and the peaks."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench.lib.harness import load_module  # noqa: E402
from bench.lib.peaks import PEAKS, peaks  # noqa: E402

roof = load_module(ROOT / "bench" / "metrics" / "fused_sinr_roofline_pct.py")


def test_work_count_is_pinned_for_one_shape():
    # 1000 dirty rows x 57 cells, one chunk, three sectors
    ops, nbytes = roof.work(1000, 57, 1, 3)
    assert ops == 1000 * 57 * (24 + 12 + 4)
    assert nbytes == 4 * (1000 * 3 + 57 * 5) + 4 * 1000 * 4
    ops1, _ = roof.work(1000, 57, 4, 1)
    assert ops1 == 1000 * 57 * (24 + 16)


def test_v5e_kernel_is_bound_by_bytes_and_share_stays_under_100():
    ops, nbytes = roof.work(625_000, 57, 1, 3)
    least = roof.least_s(ops, nbytes, "TPU v5 lite")
    assert least == nbytes / PEAKS["TPU v5 lite"].hbm_bw
    for factor in (1.0, 1.0 + 1e-9, 2.0, 1e3):
        assert roof.share_pct(ops, nbytes, least * factor,
                              "TPU v5 lite") <= 100.0
    assert roof.share_pct(ops, nbytes, least, "TPU v5 lite") == \
        pytest.approx(100.0)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(ValueError, match="no published peaks"):
        peaks("cpu")
    with pytest.raises(ValueError):
        roof.least_s(1.0, 1.0, "TPU v9")
