"""Ahead-of-time compiles of the serving path's Pallas kernels for a
described TPU v5e chip, at Qwen2.5-3B widths (Hq=16, Hkv=2, D=128, page
32, bf16). Nothing runs: the TPU compiler refuses what the chip would
refuse (block tiling, DMA shapes, VMEM), which interpret mode cannot show.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, so the whole check stays in
this one file and in the test's own process.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import flash_attention
from repro.kernels.paged_attention import (paged_attention,
                                           paged_attention_fused)
from repro.kernels.paged_flash_attention import paged_flash_attention

HQ, HKV, D, PAGE = 16, 2, 128, 32
POOL = 2049                       # the one-chip live pool plus scratch
B, MAXP = 4, 256                  # decode lanes over 8,192-token contexts
SQ, NP = 2048, 256                # a prefill chunk over 8,192 tokens


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent cache
        # but cannot be read back without one: keep these out of it
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            compilation_cache.reset_cache()


def _spec(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _decode(s):
    return (_spec(s, (B, HQ, D)), _spec(s, (POOL, PAGE, HKV, D)),
            _spec(s, (POOL, PAGE, HKV, D)), _spec(s, (B, MAXP), jnp.int32),
            _spec(s, (B,), jnp.int32))


def _fused_args(s):
    return _decode(s) + (_spec(s, (B, HKV, D)), _spec(s, (B, HKV, D)),
                         _spec(s, (B,), jnp.int32), _spec(s, (B,), jnp.int32))


def _prefill_args(s):
    return (_spec(s, (1, HQ, SQ, D)), _spec(s, (POOL, PAGE, HKV, D)),
            _spec(s, (POOL, PAGE, HKV, D)), _spec(s, (1, NP), jnp.int32),
            _spec(s, (1,), jnp.int32), _spec(s, (1,), jnp.int32))


def _flash_args(s):
    return (_spec(s, (1, HQ, SQ, D)), _spec(s, (1, HKV, SQ, D)),
            _spec(s, (1, HKV, SQ, D)))


CASES = {
    "paged_attention": (paged_attention, _decode),
    "paged_attention_fused": (paged_attention_fused, _fused_args),
    "paged_flash_attention": (paged_flash_attention, _prefill_args),
    "flash_attention": (flash_attention, _flash_args),
}


@pytest.mark.parametrize("name", list(CASES))
def test_kernel_compiles_for_v5e(one_chip, name):
    kernel, make_args = CASES[name]
    compiled = kernel.lower(*make_args(one_chip), interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
