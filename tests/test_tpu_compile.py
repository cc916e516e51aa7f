"""Main-path kernels compiled for a described TPU v5e (no chip needed),
plus the compile-cache helper.

The TPU compiler is installed with JAX and compiles for a topology that
is described, not attached; it enforces what interpret mode does not
(block tiling, VMEM limits).  The topology is described inside a fixture,
never at import, so every xdist worker collects the same tests and only
the worker running this file loads the TPU library.
"""
from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.paged_attention.kernel import paged_decode_attention_kernel
from repro.launch import compile_cache


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A described-chip compile cannot be read back from the persistent
    cache without a chip; keep it out of the cache."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.mark.parametrize("hq,hkv", [
    (16, 16),   # OLMo-1B: MHA
    (32, 8),    # GQA, group of 4
])
def test_paged_kernel_compiles_for_v5e(one_chip, no_compile_cache, hq, hkv):
    """The paged decode kernel at OLMo-1B widths (B=8, D=128, page 16,
    64 pages per request, bf16) lowers to a Mosaic custom call named
    ``paged_attention``."""
    b, d, ps, np_ = 8, 128, 16, 64
    n_phys = b * np_ + 1

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(paged_decode_attention_kernel).lower(
        sds((b, hq, d), jnp.bfloat16),
        sds((n_phys, ps, hkv, d), jnp.bfloat16),
        sds((n_phys, ps, hkv, d), jnp.bfloat16),
        sds((b, np_), jnp.int32),
        sds((b,), jnp.int32),
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # the kernel keeps its own name in the compiled program
    assert re.search(r"%paged_attention[.\d]* = .*tpu_custom_call", text)


# ------------------------------------------------------------ compile cache

@pytest.fixture
def config_updates(monkeypatch):
    """Record jax.config.update calls instead of applying them."""
    calls = []
    monkeypatch.setattr(compile_cache.jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    return calls


def test_compile_cache_env_set_is_left_alone(config_updates):
    env = {compile_cache.CACHE_ENV: "/somewhere/else"}
    assert compile_cache.setup_compile_cache(env) is None
    assert config_updates == []


def test_compile_cache_env_unset_uses_checkout_dir(config_updates):
    root = compile_cache.default_cache_dir().parent
    assert (root / "src" / "repro" / "launch" / "compile_cache.py").is_file()
    path = compile_cache.setup_compile_cache({})
    assert path == root / ".jax_cache"
    assert config_updates == [("jax_compilation_cache_dir", str(path))]
