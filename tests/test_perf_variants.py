"""§Perf optimized-variant features: function-preserving checks."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_smoke
from repro.models import build_model
from repro.models.moe import apply_moe, init_moe


def test_moe_gather_combine_equals_scatter():
    cfg = get_smoke("kimi-k2-1t-a32b")
    p = init_moe(jax.random.PRNGKey(0), cfg)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 16, cfg.d_model)),
                    jnp.float32)
    o1, _ = apply_moe(x, p, cfg)
    o2, _ = apply_moe(x, p, cfg.replace(moe_combine="gather"))
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=1e-5, atol=1e-5)


def test_int8_latent_cache_accuracy():
    """Quantized MLA cache: teacher-forced decode stays close to the
    bf16 cache after 12 steps (random-weight smoke model; the full-config
    deepseek error measured 1.1% — EXPERIMENTS.md §Perf cell 3).

    The smoke bound is 8%: random weights have no trained scale
    structure, so quantization error is dominated by outlier activations
    and lands jax-version-dependent in the 4-7% range (6.2% on the
    0.4.37 CPU build); the real accuracy gate is the measured
    full-config 1.1%."""
    cfg = get_smoke("deepseek-v2-236b")
    m = build_model(cfg)
    m8 = build_model(cfg.replace(kv_cache_dtype="int8"))
    params = m.init_params(jax.random.PRNGKey(0))
    B, T = 2, 12
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg.vocab_size, size=(B, T)).astype(np.int32)
    cA, cB = m.init_cache(B, T + 4), m8.init_cache(B, T + 4)
    assert cB["latent"].dtype == jnp.int8
    dA, dB = jax.jit(m.decode_step), jax.jit(m8.decode_step)
    for t in range(T):
        lA, cA = dA(params, {"tokens": jnp.asarray(toks[:, t:t + 1])}, cA)
        lB, cB = dB(params, {"tokens": jnp.asarray(toks[:, t:t + 1])}, cB)
    rel = float(jnp.max(jnp.abs(lA - lB)) / (jnp.max(jnp.abs(lA)) + 1e-9))
    assert rel < 0.08, rel


def test_head_padding_rules():
    from repro.launch.dryrun import opt_overrides
    from repro.configs import get_config

    phi3 = get_config("phi3-medium-14b")
    padded = opt_overrides(phi3, "train")
    assert padded.n_heads % 16 == 0
    assert padded.n_heads % padded.n_kv_heads == 0
    assert padded.n_heads >= phi3.n_heads
    # gemma MQA stays unpadded (kv=1 replicates cheaply)
    gem = opt_overrides(get_config("gemma-2b"), "train")
    assert gem.n_kv_heads == 1
    # MLA archs are untouched (latent path has no per-head KV)
    ds = opt_overrides(get_config("deepseek-v2-236b"), "train")
    assert ds.n_heads == 128


def test_partial_factorizations_never_cached():
    """Theorem 1 vs graceful degradation (Lessons L4): a budget-exceeded
    partial result must not poison the factorization cache."""
    from repro.core import Factorizer

    f = Factorizer()
    big = 1_000_003 * 1_000_033 * 1_000_037
    partial = f.factorize(big, time_budget_s=0.0)   # forced budget blow
    assert f.cache.get(big) is None or \
        np.prod([int(x) for x in f.cache.get(big)]) == big
    full = f.factorize(big, time_budget_s=10.0)
    assert full == (1_000_003, 1_000_033, 1_000_037)


def test_split_k_cache_sharding_spec():
    from jax.sharding import AbstractMesh
    from repro.configs import SHAPES, get_config
    from repro.sharding import partition as pt

    mesh = AbstractMesh((16, 16), ("data", "model"))
    cfg = get_config("qwen3-32b")          # kv=8: cannot shard 16-way
    model = build_model(cfg)
    cache = model.cache_specs(SHAPES[2])   # decode_32k
    sh = pt.cache_shardings(cache, mesh, cfg, seq_over_model=True)
    assert sh["k"].spec[2] == "model"              # sequence split-K
    assert sh["k"].spec[1] in ("data", ("data",))  # batch over data
