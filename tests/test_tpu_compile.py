"""Main-path kernels, the paged decode step and the padded prefill compiled
for a described TPU v5e (no chip needed), plus the compile-cache helper.

The TPU compiler is installed with JAX and compiles for a topology that
is described, not attached; it enforces what interpret mode does not
(block tiling, VMEM limits).  The topology is described inside a fixture,
never at import, so every xdist worker collects the same tests and only
the worker running this file loads the TPU library.
"""
from __future__ import annotations

import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import registry
from repro.kernels.paged_attention.kernel import paged_decode_attention_kernel
from repro.launch import compile_cache
from repro.models.attention import query_chunk
from repro.models.registry import get_arch
from repro.serving.paged_kv import PagedInferenceEngine, paged_step_programs


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
    (32, 2),    # ChatGLM3-6B: GQA, group of 16
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


# ------------------------------------------------- paged decode step, in place

_INSTR = re.compile(r"^\s*(?:ROOT )?%([\w.-]+) = (\S+) ([\w-]+)\(")
_COMP = re.compile(r"^(?:ENTRY )?%([\w.-]+) .*\{$")
_CALLS = re.compile(r"calls=%([\w.-]+)")


def _pool_writers(text: str, pool_dims: list[str]) -> list[tuple]:
    """Every instruction of the compiled ``text``, outside fused
    computations, whose output is one of the bf16 arrays ``pool_dims``
    (``"2,1025,16,16,128"``), bar parameters and tuple reads:
    ``[(name, opcode, root opcode of the computation it calls)]``."""
    shapes = tuple(f"bf16[{d}]" for d in pool_dims)
    found, roots, fused, comp = [], {}, set(), None
    for line in text.splitlines():
        head = _COMP.match(line)
        if head:
            comp = head.group(1)
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name, shape, op = m.groups()
        called = _CALLS.search(line)
        called = called.group(1) if called else None
        if op == "fusion":
            fused.add(called)
        if line.lstrip().startswith("ROOT "):
            roots[comp] = op
        if (shape.split("{")[0] in shapes
                and op not in ("parameter", "get-tuple-element")):
            found.append((comp, name, op, called))
    return [(name, op, roots.get(called))
            for comp, name, op, called in found if comp not in fused]


@pytest.mark.parametrize("hq,hkv", [
    (16, 16),   # OLMo-1B: MHA
    (32, 8),    # GQA, group of 4
    (32, 2),    # ChatGLM3-6B: GQA, group of 16
])
def test_paged_decode_step_updates_pool_in_place(one_chip, no_compile_cache,
                                                 monkeypatch, hq, hkv):
    """The engine's paged decode program (``_paged``, cache donated) at
    OLMo-1B widths, 2 layers, 8 lanes and the benchmark cell's 1025-page
    pool: both pools alias input to output, the only instructions that
    write a pool-shaped array are the two in-place token scatters (no
    per-layer slice, restack or copy), and temporaries stay small."""
    # the step dispatches the kernel as it does on a chip
    monkeypatch.setattr(registry.jax, "default_backend", lambda: "tpu")
    arch = get_arch("olmo-1b")
    arch = dataclasses.replace(arch, cfg=dataclasses.replace(
        arch.cfg, n_layers=2, n_heads=hq, n_kv_heads=hkv))
    paged, _ = paged_step_programs(arch, use_kernel=True, interpret=False)
    b, n_phys, ps, np_, d = 8, 1025, 16, 128, arch.cfg.hd

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(arch.init, jax.random.PRNGKey(0)))
    pool = sds((2, n_phys, ps, hkv, d), jnp.bfloat16)
    compiled = paged.lower(
        params, sds((b,), jnp.int32), {"layers": {"k": pool, "v": pool}},
        sds((b,), jnp.int32), sds((b, np_), jnp.int32),
        sds((b,), jnp.bool_), sds((b,), jnp.float32), sds((b,), jnp.int32),
    ).compile()

    mem = compiled.memory_analysis()
    pool_bytes = 2 * 2 * n_phys * ps * hkv * d * 2
    assert mem.alias_size_in_bytes == pool_bytes
    assert mem.temp_size_in_bytes < 64 * 2**20
    writers = _pool_writers(compiled.as_text(),
                            [f"2,{n_phys},{ps},{hkv},{d}",
                             f"{n_phys},{ps},{hkv},{d}"])
    assert len(writers) == 2, writers
    for _name, op, root in writers:
        assert "scatter" in (op, root), writers


# ------------------------------------------------- prefill, chunked attention

@pytest.mark.parametrize("chunk", [
    496,        # the chatglm3-6b.pp2.long-prompt-sat cell's: 8 chunks of 3968
    512,        # does not divide 3968: the last chunk is padded
])
def test_chatglm_prefill_attention_runs_chunked(one_chip, no_compile_cache,
                                                chunk):
    """The engine's padded prefill program (``_prefill``) at ChatGLM3-6B
    widths, 2 layers, the (2, 3968) batch the benchmark cell pinned
    before the prefill ladder (whose largest program is (1, 3968)) and
    ``attn_chunk`` ``chunk``: its temporaries stay under the float32
    all-position logits plus one layer's full (B, Hkv, G, S, S) float32
    scores.  The unchunked program holds both (8.1 GB); the chunked one
    holds the logits and one chunk's scores (4.1 GB)."""
    arch = get_arch("chatglm3-6b")
    arch = dataclasses.replace(arch, cfg=dataclasses.replace(
        arch.cfg, n_layers=2, attn_chunk=chunk))
    cfg = arch.cfg

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(arch.init, jax.random.PRNGKey(0)))
    b, s = 2, 3968
    eng = PagedInferenceEngine(arch, params, n_lanes=b, max_prompt_len=s,
                               max_len=4096, page_size=16)
    compiled = eng._prefill.lower(params, sds((b, s), jnp.int32),
                                  sds((b,), jnp.int32), 4096).compile()
    logits = b * s * cfg.vocab_size * 4
    scores = b * cfg.n_heads * s * s * 4
    assert query_chunk(cfg, s) == chunk
    assert compiled.memory_analysis().temp_size_in_bytes < logits + scores


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
