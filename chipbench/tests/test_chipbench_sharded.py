"""The four-chip page-manager cell: it resolves to four chips, its
roofline counts bytes per device, and at a smoke size on four virtual
CPU devices its driver matches the reference with every rebuild on the
mesh."""

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

CELL = "pfcs-pages-x4.docs-shared"
MS = 1_000_000  # ns


def test_cell_resolves_to_four_chips():
    from chipbench.harness import resolve

    cell = resolve(CELL)
    assert cell.chips == 4
    assert cell.driver == "sharded_slot_machine"
    assert (cell.config["kv"], cell.config["shards"]) == ("elastic", 4)
    assert cell.mix["fill"] == cell.config["max_batch"] == 512
    assert {m["name"] for m in cell.per_layer} == {
        "shard_rebuild_ms", "exchange_wait_ms", "all_gather_ms",
        "shard_scan_roofline"}


def _four_planes(kernel_ns, ops=()):
    """Four device planes, each running one ``divisibility_mask`` launch
    of ``kernel_ns`` and the given ops."""
    planes = {}
    for d in range(4):
        k = [("divisibility_mask", 10 * MS, 10 * MS + kernel_ns)]
        planes[f"/device:TPU:{d}"] = {"ops": [("divisibility_mask.1",) +
                                              k[0][1:]] + list(ops),
                                      "modules": [], "kernels": k}
    return planes


def _record(trace, launches, spans=()):
    from chipbench.harness import Record, load_peaks

    return Record(window=(0.0, 0.1), token_times=[], due=np.zeros(0),
                  setup_s=0.0, spans=list(spans), launches=launches,
                  trace=trace, peaks=load_peaks("TPU v5 lite"))


def test_shard_scan_roofline_counts_bytes_per_device():
    """Each plane moves one shard's bytes in its own time: the share is
    per device, where the four shards' bytes over one plane's time would
    read four times too high."""
    from chipbench import work
    from chipbench.metrics import kernel_roofline, shard_scan_roofline
    from chipbench.reduce_trace import reduce_events

    launch = ("divisibility_mask", 3_000, 11, 2_600)
    nbytes = work.of("divisibility_mask").hbm_bytes(*launch[1:])
    # 60% of the roofline in each plane's own time
    kernel_ns = int(nbytes / 819e9 / 0.6 * 1e9)
    trace = reduce_events(_four_planes(kernel_ns), [("window", 0, 100 * MS)])
    rec = _record(trace, [launch] * 4)
    share = shard_scan_roofline.read(rec)
    assert share == pytest.approx(60.0, rel=1e-3)
    assert kernel_roofline(rec, "divisibility_mask") == pytest.approx(
        4 * share)
    assert shard_scan_roofline.read(_record(trace, [])) is None


def test_all_gather_ms_is_per_device_and_per_exchange():
    from chipbench.metrics import all_gather_ms
    from chipbench.reduce_trace import reduce_events

    gathers = [("all-gather-start.1", 40 * MS, 41 * MS),
               ("all-gather-done.1", 41 * MS, 42 * MS),
               ("all-gather.5", 60 * MS, 62 * MS)]
    trace = reduce_events(_four_planes(MS, gathers),
                          [("window", 0, 100 * MS)])
    exchanges = [("kernels.sharded_exchange", 0.03, 0.05),
                 ("kernels.sharded_exchange", 0.055, 0.07)]
    assert all_gather_ms.read(_record(trace, [], exchanges)) == \
        pytest.approx(2.0)
    assert all_gather_ms.read(_record(trace, [])) is None


_SMOKE = r"""
import json, os, sys, tempfile, time
from pathlib import Path
ROOT = Path(sys.argv[1])
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
from chipbench.harness import run

d = Path(tempfile.mkdtemp())
bench = json.loads((ROOT / "BENCHMARK.json").read_text())
cfg = json.loads((ROOT / "chipbench/configs/pfcs-pages-x4.json").read_text())
cfg.update(max_batch=8, hbm_pages=256, prefill_tokens=256)
(d / "cfg.json").write_text(json.dumps(cfg))
mix = json.loads((ROOT / "chipbench/traffic/docs-shared-x4.json").read_text())
mix.update(fill=8, arrival={"process": "steady", "backlog": 8,
                            "rate_per_s": 40.0},
           prefixes={"count": 5, "len_min": 32, "len_max": 128, "zipf": 1.0},
           suffix={"median": 24, "sigma": 0.8, "min": 8, "max": 64},
           output={"median": 5, "sigma": 0.5, "min": 2, "max": 12},
           block=16)
(d / "traffic").mkdir()
(d / "traffic" / "docs-shared-x4.json").write_text(json.dumps(mix))
next(c for c in bench["configs"]
     if c["name"] == "pfcs-pages-x4")["file"] = str(d / "cfg.json")
(d / "bench.json").write_text(json.dumps(bench))
results = []
for trace in (False, True):
    sessions = []
    out = run("pfcs-pages-x4.docs-shared", 2 ** 31 + 7, 1.0, trace,
              started=time.perf_counter(), require_tpu=False,
              prepare=sessions.append, bench_file=d / "bench.json",
              traffic_dir=d / "traffic", log=open(os.devnull, "w"))
    s = sessions[0]
    out["on_mesh"] = s.on_mesh
    out["launches"] = s.launches
    results.append(out)
print(json.dumps(results))
"""


def test_driver_matches_reference_on_four_virtual_devices():
    from chipbench.harness import _within

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", _SMOKE, str(ROOT)], env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr[-3000:]
    timed, traced = json.loads(p.stdout.strip().splitlines()[-1])
    for out in (timed, traced):
        checked = out["checked"]
        assert out["correct"] and out["failed"] == 0, checked
        for name, c in checked.items():
            assert _within(c["value"], c["limit"]), (name, c)
        assert checked["host_loop_rebuilds"]["value"] == 0
        assert out["on_mesh"] and all(out["on_mesh"])
    assert set(timed["metrics"]) == {"output_tok_per_s", "tbt_p95_ms",
                                     "setup_s"}
    # the program's spans reach the harness in a traced run
    assert {"shard_rebuild_ms", "exchange_wait_ms"} <= set(traced["metrics"])
    shards = [l for l in traced["launches"] if l[0] == "divisibility_mask"]
    assert shards and len(shards) % 4 == 0
