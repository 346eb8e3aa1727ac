"""Operation and byte counts of the kernels, and the table of peaks."""

import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT)]

from chipbench import work  # noqa: E402
from chipbench.harness import Record  # noqa: E402
from chipbench.metrics import kernel_roofline  # noqa: E402


KERNELS = sorted(p.stem for p in (ROOT / "chipbench" / "work").glob("*.py")
                 if p.stem != "__init__")


def test_divisibility_counts():
    k = work.of("divisibility_mask")
    assert k.ops(100, 11, 1024) == 3 * 100 * 11 * 1024
    assert k.hbm_bytes(100, 11, 1024) == \
        4 * 100 * 11 + 4 * 1024 + 100 * 1024


def test_factorize_counts():
    k = work.of("factorize_squarefree")
    assert k.ops(8, 11, 512) == 3 * 8 * 11 * 512
    assert k.hbm_bytes(8, 11, 512) == 2 * 4 * 8 * 11 + 4 * 512 + 8 * 512


@pytest.mark.parametrize("kernel", KERNELS)
def test_counts_grow_with_the_problem_not_padding(kernel):
    k = work.of(kernel)
    assert k.hbm_bytes(301, 11, 700) > k.hbm_bytes(300, 11, 700)
    assert k.ops(301, 11, 700) > k.ops(300, 11, 700)


def test_peaks_table_has_v5e():
    peaks = json.loads((ROOT / "chipbench" / "peaks.json").read_text())
    v5e = peaks["devices"]["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["int8_ops_per_s"] == 393e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["hbm_bytes"] == 16e9
    assert "Google Cloud" in peaks["source"]


def test_roofline_share_from_trace_and_launches():
    rec = Record(window=(0.0, 1.0), token_times=[], due=np.zeros(0),
                 setup_s=0.0,
                 launches=[("divisibility_mask", 1000, 11, 1024)] * 2,
                 trace={"kernels": {"divisibility_mask":
                                    {"seconds": 1e-3, "events": 2}}},
                 peaks={"hbm_bytes_per_s": 819e9})
    nbytes = 2 * work.of("divisibility_mask").hbm_bytes(1000, 11, 1024)
    assert kernel_roofline(rec, "divisibility_mask") == \
        pytest.approx(100 * nbytes / 819e9 / 1e-3)
    assert kernel_roofline(rec, "factorize_squarefree") is None
