"""Smoke tests for the serving launcher + example (ISSUE 8 satellite:
``examples/serve_lm.py`` must drive the SlotMachine front-end by
default and can't silently rot again)."""

import ast
import pathlib

from repro.launch.serve import main as serve_main

ROOT = pathlib.Path(__file__).resolve().parent.parent


def test_launcher_defaults_to_slot_machine():
    out = serve_main(["--null-model", "--requests", "24", "--max-new", "4",
                      "--max-batch", "8", "--shared-prefix", "16"])
    assert out["front_end"] == "slots"
    assert out["completed"] == 24
    assert out["decode_tokens"] == 24 * 4
    assert out["ticks"] > 0
    # the whole point of the PFCS cache: prefix sharing + prefetch fire
    assert out["shared_prefix_pages"] > 0
    assert out["prefetches"] > 0


def test_launcher_engine_front_end_still_available():
    out = serve_main(["--null-model", "--front-end", "engine",
                      "--requests", "8", "--max-new", "4",
                      "--max-batch", "4", "--shared-prefix", "16"])
    assert out["front_end"] == "engine"
    assert out["completed"] == 8


def test_launcher_slots_wide_registry():
    # --max-bits > 63: the SlotMachine composes with the multi-limb
    # wide registry (DESIGN.md §11) — same counters as narrow
    narrow = serve_main(["--null-model", "--requests", "16",
                         "--max-new", "4", "--max-batch", "8",
                         "--shared-prefix", "16"])
    wide = serve_main(["--null-model", "--requests", "16",
                       "--max-new", "4", "--max-batch", "8",
                       "--shared-prefix", "16", "--max-bits", "128"])
    for k in ("completed", "decode_tokens", "ticks", "hbm_hit_rate",
              "prefetches", "prefetch_hits", "shared_prefix_pages"):
        assert narrow[k] == wide[k], k


def test_example_script_drives_the_launcher():
    """The example must keep routing through ``launch.serve.main`` (so
    the launcher smoke tests above cover it) and must not pin
    ``--front-end engine`` on its load-generator pass."""
    src = (ROOT / "examples" / "serve_lm.py").read_text()
    tree = ast.parse(src)        # it parses
    assert "serve_main" in src
    null_model_calls = [n for n in ast.walk(tree)
                        if isinstance(n, ast.Call)
                        and any(isinstance(a, ast.List) and any(
                            isinstance(e, ast.Constant)
                            and e.value == "--null-model"
                            for e in a.elts) for a in n.args)]
    assert null_model_calls, "example lost its load-generator pass"
    for call in null_model_calls:
        flags = [e.value for a in call.args if isinstance(a, ast.List)
                 for e in a.elts if isinstance(e, ast.Constant)]
        assert "--front-end" not in flags, \
            "load-generator pass must use the default (slots) front-end"


def test_launcher_smoke_flag_selects_the_config(monkeypatch):
    """``--smoke`` asks for the reduced config; without it the launcher
    builds the published widths of ``--arch`` (the flag used to be
    stuck on)."""
    import repro.launch.serve as serve

    asked = []

    class Built(Exception):
        pass

    def fake_build(arch, smoke, seed=0):
        asked.append((arch, smoke))
        raise Built

    monkeypatch.setattr(serve, "build_model", fake_build)
    for argv, want in ((["--arch", "gemma-2b"], ("gemma-2b", False)),
                       (["--arch", "gemma-2b", "--smoke"],
                        ("gemma-2b", True))):
        try:
            serve_main(argv)
        except Built:
            pass
        assert asked.pop() == want


def test_launcher_serves_a_smoke_model_end_to_end():
    out = serve_main(["--arch", "qwen2.5-3b", "--smoke", "--requests", "3",
                      "--max-new", "3", "--max-batch", "2", "--max-seq",
                      "64", "--shared-prefix", "8"])
    assert out["front_end"] == "engine"
    assert out["completed"] == 3 and out["decode_tokens"] == 9


def test_compile_cache_defers_to_the_environment(monkeypatch):
    """With ``JAX_COMPILATION_CACHE_DIR`` set, JAX reads it itself and
    the entry points' helper sets no directory in code; it still keeps
    programs of any compile time."""
    import jax

    from repro.launch.compile_cache import (DEFAULT_CACHE_DIR,
                                            enable_compile_cache)

    before = jax.config.jax_compilation_cache_dir
    min_secs = jax.config.jax_persistent_cache_min_compile_time_secs
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    try:
        assert enable_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == before
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          min_secs)
    assert DEFAULT_CACHE_DIR == ROOT / ".jax_cache"
