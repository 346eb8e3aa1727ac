"""Sharding rules: every leaf gets a valid spec on the production mesh
shapes (divisibility fallback never produces an invalid partition)."""

import numpy as np
import pytest

import jax
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs import ARCH_IDS, get_config, get_smoke
from repro.models import build_model
from repro.sharding import partition as pt
from repro.training.train_loop import abstract_train_state


def _fake_mesh(shape, axes):
    """AbstractMesh carries axis sizes without needing real devices."""
    from jax.sharding import AbstractMesh
    return AbstractMesh(shape, axes)


MESH = _fake_mesh((16, 16), ("data", "model"))
MESH3 = _fake_mesh((2, 16, 16), ("pod", "data", "model"))


def _check_specs(abstract_tree, shardings, mesh):
    leaves_a = jax.tree.leaves(abstract_tree)
    leaves_s = jax.tree.leaves(
        shardings, is_leaf=lambda x: hasattr(x, "spec"))
    assert len(leaves_a) == len(leaves_s)
    for arr, sh in zip(leaves_a, leaves_s):
        spec = sh.spec
        assert len(spec) <= arr.ndim, (arr.shape, spec)
        for dim, entry in zip(arr.shape, spec):
            if entry is None:
                continue
            axes = (entry,) if isinstance(entry, str) else entry
            total = int(np.prod([mesh.shape[a] for a in axes]))
            assert dim % total == 0, (arr.shape, spec)


@pytest.mark.parametrize("arch_id", ARCH_IDS)
@pytest.mark.parametrize("mesh", [MESH, MESH3], ids=["pod", "multipod"])
def test_param_shardings_valid_full_config(arch_id, mesh):
    cfg = get_config(arch_id)
    model = build_model(cfg)
    params = model.param_specs()
    sh = pt.params_shardings(params, mesh, cfg)
    _check_specs(params, sh, mesh)


@pytest.mark.parametrize("arch_id", ["qwen3-32b", "kimi-k2-1t-a32b",
                                     "zamba2-7b", "xlstm-1.3b"])
def test_opt_state_shardings_valid(arch_id):
    cfg = get_config(arch_id)
    model = build_model(cfg)
    state = abstract_train_state(model)
    sh = pt.opt_state_shardings(state.opt_state, state.params, MESH, cfg)
    _check_specs(state.opt_state, sh, MESH)


@pytest.mark.parametrize("arch_id", ["gemma-2b", "deepseek-v2-236b",
                                     "zamba2-7b"])
def test_cache_shardings_valid(arch_id):
    from repro.configs import SHAPES
    cfg = get_config(arch_id)
    model = build_model(cfg)
    shape = SHAPES[2]  # decode_32k
    cache = model.cache_specs(shape)
    sh = pt.cache_shardings(cache, MESH, cfg)
    _check_specs(cache, sh, MESH)


def test_seq_shard_long_context():
    from repro.configs import SHAPES
    cfg = get_config("zamba2-7b")
    model = build_model(cfg)
    shape = SHAPES[3]  # long_500k, batch=1
    cache = model.cache_specs(shape)
    sh = pt.cache_shardings(cache, MESH, cfg, seq_shard=True)
    _check_specs(cache, sh, MESH)
    # the KV caches must actually be sequence-sharded
    k_sh = sh["k"]
    assert k_sh.spec[2] is not None


def test_tp_weights_are_sharded_over_model():
    cfg = get_config("qwen3-32b")
    model = build_model(cfg)
    params = model.param_specs()
    sh = pt.params_shardings(params, MESH, cfg)
    wq = sh["dense_layers"]["attn"]["wq"].spec
    assert "model" in jax.tree.leaves(tuple(wq))
    emb = sh["embed"]["table"].spec
    assert emb[0] == "model"                 # vocab sharded


def test_gemma_mqa_kv_fallback():
    """gemma kv=1 cannot shard heads over model=16 -> falls back without
    producing an invalid spec (head_dim 256 divides instead)."""
    cfg = get_config("gemma-2b")
    model = build_model(cfg)
    params = model.param_specs()
    sh = pt.params_shardings(params, MESH, cfg)
    wk = sh["dense_layers"]["attn"]["wk"].spec
    # (L, d, kv=1, hd=256): kv dim must NOT be sharded
    assert wk[2] is None


def test_divisibility_fallback_warns_exactly_once(caplog):
    """The replicate fallback logs ONE warning per distinct
    (dim, axes, size) — not one per layer, not zero: gemma's single KV
    head (1 vs model=16) appears in every attention block but must
    surface exactly once, and a repeat run adds nothing."""
    import logging

    cfg = get_config("gemma-2b")
    model = build_model(cfg)
    params = model.param_specs()
    pt.reset_fallback_warnings()
    with caplog.at_level(logging.WARNING, logger=pt.log.name):
        pt.params_shardings(params, MESH, cfg)
    kv_head = [r for r in caplog.records
               if "dim 1 does not divide" in r.getMessage()
               and "'model'" in r.getMessage()]
    assert len(kv_head) == 1, [r.getMessage() for r in caplog.records]
    n_first = len(caplog.records)
    assert n_first >= 1
    with caplog.at_level(logging.WARNING, logger=pt.log.name):
        pt.params_shardings(params, MESH, cfg)   # dedup across calls
    assert len(caplog.records) == n_first
    pt.reset_fallback_warnings()
