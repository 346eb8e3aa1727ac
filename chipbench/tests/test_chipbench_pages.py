"""The page-manager cell at a smoke size on the CPU: the comparison with
the plain reference passes on the program as configured and fails on
the control and on each fault planted under the timed path; ``run.py``
refuses any backend but the TPU."""

import json
import os
import subprocess
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

CELL = "pfcs-pages.docs-shared"
SEED = 2 ** 31 + 101


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """BENCHMARK.json with the cell cut to a smoke size."""
    d = tmp_path_factory.mktemp("tiny")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg = json.loads((ROOT / "chipbench/configs/pfcs-pages.json").read_text())
    cfg.update(max_batch=6, hbm_pages=40, prefill_tokens=192)
    (d / "cfg.json").write_text(json.dumps(cfg))
    mix = json.loads((ROOT / "chipbench/traffic/docs-shared.json")
                     .read_text())
    mix.update(fill=6, arrival={"process": "steady", "backlog": 6,
                                "rate_per_s": 40.0},
               prefixes={"count": 5, "len_min": 32, "len_max": 128,
                         "zipf": 1.0},
               suffix={"median": 24, "sigma": 0.8, "min": 8, "max": 64},
               output={"median": 5, "sigma": 0.5, "min": 2, "max": 12},
               block=16)
    (d / "traffic").mkdir()
    (d / "traffic" / "docs-shared.json").write_text(json.dumps(mix))
    w = next(w for w in bench["workloads"] if w["name"] == CELL)
    next(c for c in bench["configs"]
         if c["name"] == w["config"])["file"] = str(d / "cfg.json")
    (d / "bench.json").write_text(json.dumps(bench))
    return d


@pytest.fixture
def restore_ops(monkeypatch):
    """Traced runs and planted faults rebind the kernel wrappers."""
    import repro.kernels.ops as ops

    for name in ("divisibility_scan", "factorize_batch"):
        monkeypatch.setattr(ops, name, getattr(ops, name))
    return ops


def _run(tiny, trace=False, control=False, prepare=None, seconds=0.6):
    from chipbench.harness import run

    return run(CELL, SEED, seconds, trace, started=time.perf_counter(),
               require_tpu=False, control=control, prepare=prepare,
               bench_file=tiny / "bench.json",
               traffic_dir=tiny / "traffic", log=open(os.devnull, "w"))


def _mismatches(out):
    return {k: v["value"] for k, v in out["checked"].items()
            if k.endswith("mismatches") and v["value"]}


@pytest.mark.parametrize("trace", [False, True], ids=["timed", "traced"])
def test_program_matches_reference(tiny, restore_ops, trace):
    out = _run(tiny, trace=trace)
    checked = out["checked"]
    assert not _mismatches(out), checked
    assert checked["scan_launches_in_window"]["value"] >= 1
    assert checked["window_ticks"]["value"] >= 1
    assert out["attempted"] > 0 and out["failed"] == 0
    names = set(out["metrics"])
    if trace:
        assert {"touch_batch_ms", "refresh_share", "queue_wait_p95_s"} <= names
        assert "breakdown" in out and "busy_s" in out["device"]
    else:
        assert names == {"output_tok_per_s", "tbt_p95_ms", "setup_s"}
    assert list(out)[-1] == "checked"


def _touch_unchanged(session):
    """touch_batch answers without moving any page."""
    inner = session.pages.touch_batch

    def frozen(items):
        return inner([]) or ["hbm"] * len(items)

    session.pages.touch_batch = frozen


def _half_batch(session):
    inner = session.pages.touch_batch
    session.pages.touch_batch = lambda items: inner(items[:len(items) // 2])


def _step_unchanged(session):
    """After set-up, a step returns with the machine's state unchanged."""
    setup = session.setup

    def then_freeze():
        setup()
        session.machine.step = lambda: {"live": 0}

    session.setup = then_freeze


def _kernel_answer_altered(session):
    """The divisibility scan drops one candidate where it is produced."""
    import repro.kernels.ops as ops

    scan = ops.divisibility_scan

    def altered(*a, **kw):
        out = scan(*a, **kw)
        for i, idx in enumerate(out):
            if len(idx):
                out[i] = idx[1:]
                break
        return out

    ops.divisibility_scan = altered


def _token_altered(session):
    """A completed request's token is altered where it is produced."""
    import repro.serving.slots as slots

    real = slots._stub_tokens

    def altered(req_id, n):
        toks = real(req_id, n)
        if req_id == 1 and toks:
            toks[-1] += 1
        return toks

    orig = session.machine.step

    def step():
        slots._stub_tokens = altered
        try:
            return orig()
        finally:
            slots._stub_tokens = real

    session.machine.step = step


def _no_kernel(session):
    """After set-up the registry refresh runs on the host: no kernel
    launch in the window."""
    setup = session.setup

    def then_host():
        setup()
        session.pages.discover = "host"

    session.setup = then_host


FAULTS = {"touch_unchanged": _touch_unchanged, "half_batch": _half_batch,
          "step_unchanged": _step_unchanged,
          "kernel_answer_altered": _kernel_answer_altered,
          "token_altered": _token_altered, "no_kernel_launch": _no_kernel}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_under_timed_path_is_not_correct(tiny, restore_ops, fault):
    out = _run(tiny, prepare=FAULTS[fault])
    assert out["correct"] is False, out["checked"]


def test_control_is_not_correct(tiny, restore_ops):
    out = _run(tiny, control=True, seconds=1.0)
    assert out["correct"] is False
    assert _mismatches(out)


def test_refuses_cpu_backend(tiny):
    from chipbench.harness import run

    with pytest.raises(SystemExit, match="needs 1 TPU chip"):
        run(CELL, SEED, 1.0, False, started=time.perf_counter(),
            bench_file=tiny / "bench.json", traffic_dir=tiny / "traffic")


def test_run_py_exits_nonzero_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(ROOT / "chipbench/run.py"),
                        "--workload", CELL, "--seed", "1", "--seconds", "1",
                        "--trace", "0"], env=env, capture_output=True,
                       text=True, timeout=120)
    assert p.returncode != 0
    assert not p.stdout.strip()


def test_run_py_exits_nonzero_without_the_program(tmp_path):
    import shutil

    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run([sys.executable, "chipbench/run.py", "--workload",
                        CELL, "--seed", "1", "--seconds", "1", "--trace",
                        "0"], cwd=tmp_path, env=env, capture_output=True,
                       text=True, timeout=120)
    assert p.returncode != 0
    assert not p.stdout.strip()
