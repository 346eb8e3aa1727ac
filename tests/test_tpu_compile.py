"""Ahead-of-time compiles for a described TPU v5e — no chip needed.

The TPU compiler is installed with JAX, and it compiles for a chip that
is described, not attached.  What it refuses here (a Mosaic lowering, a
tiling rule, a program too large for 16 GiB of HBM) it would refuse on
the chip, so these tests guard the device path on every change:

* each discovery kernel at 4,096 composites x 1,024 primes, with
  narrow (62-bit) and wide (1,024-bit) digit rows, and the scan and
  the factorization with their masks compacted on the device;
* the sharded exchange's ``shard_map`` scan over a 2x2 mesh, each
  shard's hits compacted on its chip, at the four-chip page-manager
  cell's buckets;
* the full-width ``qwen2.5-3b`` decode step and parameter init, which
  must fit one chip.

The topology is described inside a module fixture (never at import),
so only the worker that runs this file loads the TPU library.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding

from repro.core.composite import n_limbs_for_bits

N_COMPOSITES, N_PRIMES = 4096, 1024
HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _is_kernel_program(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("bits", [62, 1024])
@pytest.mark.parametrize("kernel", ["divisibility_mask", "factorize",
                                    "gcd_squarefree", "divisibility_hits",
                                    "factorize_hits"])
def test_discovery_kernel_compiles(one_chip, kernel, bits):
    from repro.kernels.factorize import (divisibility_hits_pallas,
                                         divisibility_mask_pallas,
                                         factorize_hits_pallas,
                                         factorize_squarefree_pallas)
    from repro.kernels.gcd import gcd_squarefree_pallas

    digits = _sds((N_COMPOSITES, n_limbs_for_bits(bits)), jnp.int32,
                  one_chip)
    primes = _sds((N_PRIMES,), jnp.int32, one_chip)
    fn, args = {
        "divisibility_mask": (divisibility_mask_pallas, (digits, primes)),
        "factorize": (factorize_squarefree_pallas, (digits, primes)),
        "gcd_squarefree": (gcd_squarefree_pallas, (digits, digits, primes)),
        "divisibility_hits": (divisibility_hits_pallas, (digits, primes)),
        "factorize_hits": (factorize_hits_pallas, (digits, primes)),
    }[kernel]
    assert _is_kernel_program(fn.lower(*args).compile())


@pytest.mark.parametrize("C", [4096, 8192])
def test_shard_map_scan_compiles_on_four_chips(topo, C):
    """The exchange at the four-chip page-manager cell's buckets: local
    rows C (and the next bucket), 1,024 gathered cross rows, 4,096
    query primes a shard.  Each shard returns its compacted hit vector
    and its query-major mask, laid end to end over the mesh."""
    from repro.core.engine.shard import _shard_map_scan
    from repro.kernels.factorize import HITS_PER_ROW
    from repro.sharding.partition import shard_stack_spec

    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2), ("data", "model"))
    stack = NamedSharding(mesh, shard_stack_spec(mesh))
    S, Q, X = 4, 4096, 1024
    L = n_limbs_for_bits(62)
    fn = _shard_map_scan(mesh, False)
    lowered = fn.lower(_sds((S, C, L), jnp.int32, stack),
                       _sds((S, Q), jnp.int32, stack),
                       _sds((S, X // S, L), jnp.int32, stack))
    hits, mask = lowered.out_info
    assert (hits.shape, hits.dtype) == ((S * (1 + Q + HITS_PER_ROW * Q),),
                                        jnp.int32)
    assert (mask.shape, mask.dtype) == ((S * Q, C + X), jnp.bool_)
    compiled = lowered.compile()
    text = compiled.as_text()
    assert "all-gather" in text and "tpu_custom_call" in text
    assert 0 < _device_bytes(compiled) < HBM_BYTES


def _qwen25_3b(one_chip):
    from repro.configs import get_config
    from repro.models import build_model

    model = build_model(get_config("qwen2.5-3b"))
    params = jax.tree.map(lambda s: _sds(s.shape, s.dtype, one_chip),
                          jax.eval_shape(model.init_params,
                                         jax.random.PRNGKey(0)))
    return model, params


def _device_bytes(compiled) -> int:
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)


def test_qwen25_3b_decode_step_fits_one_chip(one_chip):
    model, params = _qwen25_3b(one_chip)
    cache = jax.tree.map(lambda s: _sds(s.shape, s.dtype, one_chip),
                         jax.eval_shape(lambda: model.init_cache(8, 2048)))
    tokens = {"tokens": _sds((8, 1), jnp.int32, one_chip)}
    compiled = jax.jit(model.decode_step).lower(params, tokens,
                                                cache).compile()
    assert 0 < _device_bytes(compiled) < HBM_BYTES


def test_qwen25_3b_param_init_fits_one_chip(one_chip):
    model, _ = _qwen25_3b(one_chip)
    key = _sds((2,), jnp.uint32, one_chip)
    compiled = jax.jit(model.init_params).lower(key).compile()
    assert 0 < _device_bytes(compiled) < HBM_BYTES
