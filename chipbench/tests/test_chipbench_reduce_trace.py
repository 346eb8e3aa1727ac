"""The trace reduction: busy-interval union, device time per program and
per kernel, and idle gaps named by the harness span open in them."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench.reduce_trace import reduce_events, union  # noqa: E402

MS = 1_000_000  # ns


def test_union_merges_overlaps_and_drops_empty():
    assert union([(5, 7), (0, 2), (1, 3), (9, 9)]) == [(0, 3), (5, 7)]


def _synthetic():
    ops = [("fusion.1", 10 * MS, 22 * MS),
           ("divisibility_mask.1", 15 * MS, 30 * MS),   # overlaps fusion.1
           ("factorize_squarefree.3", 50 * MS, 60 * MS),
           ("fusion.1", 95 * MS, 130 * MS)]            # runs past the window
    kernels = [("divisibility_mask", 15 * MS, 30 * MS),
               ("factorize_squarefree", 50 * MS, 60 * MS)]
    modules = [("jit_scan", 10 * MS, 30 * MS), ("jit_fact", 50 * MS, 60 * MS)]
    spans = [("window", 0, 100 * MS),
             ("engine_step", 0, 100 * MS),
             ("touch_batch", 30 * MS, 50 * MS),
             ("refresh_tables", 35 * MS, 45 * MS)]
    return {"/device:TPU:0": {"ops": ops, "modules": modules,
                              "kernels": kernels}}, spans


def test_reduce_synthetic_events():
    devices, spans = _synthetic()
    r = reduce_events(devices, spans)
    assert r["window_s"] == pytest.approx(0.100)
    # busy: [10, 30] + [50, 60] + [95, 100] ms
    assert r["busy_s"] == pytest.approx(0.035)
    assert r["kernels"]["divisibility_mask"] == {"seconds":
                                                 pytest.approx(0.015),
                                                 "events": 1}
    assert r["kernels"]["factorize_squarefree"]["seconds"] == \
        pytest.approx(0.010)
    assert r["program_seconds"] == {"jit_scan": pytest.approx(0.020),
                                    "jit_fact": pytest.approx(0.010)}
    idle = r["idle_by_span"]
    # gaps [0,10] and [60,95] under engine_step only; [30,50] has its
    # midpoint inside refresh_tables, the innermost span open there
    assert idle["engine_step"] == pytest.approx(0.045)
    assert idle["refresh_tables"] == pytest.approx(0.020)
    assert sum(idle.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    assert r["idle_gaps"] == 3
    assert r["top_ops"][0][0] == "fusion.1"
    assert len(r["top_ops"]) <= 10 and len(r["top_idle"]) <= 10


def test_devices_are_averaged():
    devices, spans = _synthetic()
    two = {"/device:TPU:0": devices["/device:TPU:0"],
           "/device:TPU:1": {"ops": [], "modules": []}}
    r = reduce_events(two, spans)
    assert r["busy_s"] == pytest.approx(0.035 / 2)
    assert r["devices"] == 2


def test_recorded_v5e_trace():
    """A trace recorded on one TPU v5 lite: two divisibility scans and two
    factorizations inside refresh_tables spans, then a touch_batch span
    with the device idle, twice over."""
    from chipbench.reduce_trace import reduce_file

    r = reduce_file(Path(__file__).parent / "data" / "v5e_kernels.xplane.pb",
                    ("window", "engine_step", "touch_batch",
                     "refresh_tables"))
    assert r["devices"] == 1
    assert 0.05 < r["window_s"] < 0.2
    assert 0 < r["busy_s"] < r["window_s"]
    k = r["kernels"]
    # every custom call is a kernel, by its instruction's name
    assert set(k) == {"divisibility_mask", "factorize_squarefree"}
    assert k["divisibility_mask"]["events"] >= 2
    assert k["factorize_squarefree"]["events"] == 2
    assert k["factorize_squarefree"]["seconds"] > 0
    kernel_s = sum(v["seconds"] for v in k.values())
    assert kernel_s <= r["busy_s"] + 1e-9
    progs = r["program_seconds"]
    assert any(p.startswith("jit_divisibility_mask_pallas") for p in progs)
    assert any(p.startswith("jit_factorize_squarefree_pallas") for p in progs)
    idle = r["idle_by_span"]
    assert set(idle) <= {"engine_step", "refresh_tables", "touch_batch",
                         "none"}
    assert idle["touch_batch"] > 0.03 and idle["refresh_tables"] > 0.01
    assert sum(idle.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    assert r["top_ops"][0][0].startswith("factorize_squarefree")
