"""ChatGLM3-6B's block kinds at a CPU size, served through the paged engine
and compared with the benchmark's plain float32 reference.

The model has ChatGLM's kinds at small widths: 32 query heads over 2 kv
heads (G = 16), head 16, QKV bias, rotary on the first half of each head,
RMSNorm and an untied head.  ``attn_chunk`` 12 does not divide the 32-row
prefill rung, so each prefill's attention runs three chunks, the last
one padded.  Also here: the chunked attention equals the unchunked one at
lengths that are not a multiple of the chunk, and the program's half-dim
rotary equals ChatGLM's interleaved one under a fixed permutation of the
rotated channels.
"""
from __future__ import annotations

import dataclasses
import functools
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.strategies import OneOrAll
from repro.models import transformer
from repro.models.attention import attention, query_chunk
from repro.models.layers import apply_rope_half
from repro.models.paged_decode import paged_decode_step
from repro.models.registry import get_arch
from repro.serving.paged_kv import PagedInferenceEngine
from repro.serving.request import Request
from repro.serving.scheduler import ContinuousBatchingScheduler

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import reference  # noqa: E402
from chipbench.model import make_weights  # noqa: E402

# The reference's ``model`` block at ChatGLM's kinds and CPU widths, with
# ChatGLM's published RMSNorm epsilon, which the program's configuration
# carries too.
MODEL = {"n_layers": 2, "d_model": 64, "n_heads": 32, "n_kv_heads": 2,
         "head_dim": 16, "d_ff": 128, "vocab_size": 256, "norm": "rmsnorm",
         "norm_eps": 1e-5, "act": "swiglu", "rope": "half",
         "rope_theta": 10000.0, "qkv_bias": True, "tie_embeddings": False,
         "dtype": "float32"}
CHUNK = 12
PROMPTS = (27, 13)      # one (1, 32) rung program each
MAX_NEW = 10            # the first token from the prefill, 9 decode steps
# Both sides compute in float32 (the reference at HIGHEST), so only the
# order of summation differs: about 2e-6 of the logits' scale here.  The
# same program in bfloat16 reads about 2e-2 (test below), so 1e-4 tells
# the two apart with room on both sides.
LOGIT_TOL = 1e-4


def _arch(attn_chunk: int = CHUNK, dtype: str = "float32"):
    arch = get_arch("chatglm3-6b")
    cfg = dataclasses.replace(
        arch.cfg.reduced(), n_layers=MODEL["n_layers"],
        d_model=MODEL["d_model"], n_heads=MODEL["n_heads"],
        n_kv_heads=MODEL["n_kv_heads"], head_dim=MODEL["head_dim"],
        d_ff=MODEL["d_ff"], vocab_size=MODEL["vocab_size"],
        param_dtype=dtype, compute_dtype=dtype, attn_chunk=attn_chunk)
    return dataclasses.replace(arch, cfg=cfg)


@pytest.fixture(scope="module")
def weights():
    return make_weights(MODEL, seed=2**31 + 11)


def _prompts():
    rng = np.random.default_rng(5)
    return [rng.integers(1, MODEL["vocab_size"], size=n).astype(np.int32)
            for n in PROMPTS]


def _serve(arch, params, use_kernel: bool):
    """Serve the prompts through the scheduler and the paged engine; every
    paged decode step's logits are read, as it runs, from the pools and
    tables it was handed."""
    eng = PagedInferenceEngine(arch, params, n_lanes=2, max_prompt_len=32,
                               max_len=64, page_size=8,
                               use_kernel=use_kernel, interpret=True)
    read = jax.jit(functools.partial(paged_decode_step, arch.cfg,
                                     use_kernel=use_kernel, interpret=True))
    step, steps = eng._paged_decode, []

    def spy(params, token, cache, lengths, tables, active, temps, seeds):
        logits, _ = read(params, token, cache, tables, lengths, active)
        rid = {lane: meta[0] for lane, meta in eng._lane_meta.items()}
        # copies: on the CPU a device array may share the engine's host
        # buffers, which it changes as lanes retire
        steps.append((np.array(logits), np.array(token), np.array(lengths),
                      np.array(active), rid))
        return step(params, token, cache, lengths, tables, active, temps,
                    seeds)

    eng._paged_decode = spy
    sched = ContinuousBatchingScheduler(eng, strategy=OneOrAll())
    reqs = [Request(rid=i, prompt=p, max_new_tokens=MAX_NEW, template="glm")
            for i, p in enumerate(_prompts())]
    for r in reqs:
        sched.submit(r)
    sched.producer_done()
    sched.run_until_drained()
    return eng, reqs, steps


def _reference(params, seq: np.ndarray, rows) -> np.ndarray:
    return reference.logits_at(MODEL, params, seq, np.asarray(rows), 64)


@pytest.mark.parametrize("use_kernel", [True, False],
                         ids=["kernel", "reference"])
def test_paged_engine_matches_the_float32_reference(weights, use_kernel):
    """Padded prefill (chunked attention), page insert and 9 paged decode
    steps at G = 16: every logit the engine computes is the reference's."""
    arch = _arch()
    eng, reqs, steps = _serve(arch, weights, use_kernel)
    assert eng.template_shapes["glm"] == (2, 32)
    assert eng.prefill_rung_programs == {32: 2}
    assert eng.prefill_attn_chunks == 2 * 3      # ceil(32 / 12) a prompt
    assert len(steps) == MAX_NEW - 1
    for r in reqs:
        assert len(r.generated) == MAX_NEW
    seqs = [np.concatenate([r.prompt, r.generated]).astype(np.int32)
            for r in reqs]

    # prefill: all-position logits of each prompt's rung program, as the
    # engine's _prefill computes them, at the prompt's last token
    for i, r in enumerate(reqs):
        n = len(r.prompt)
        toks = np.zeros((1, 32), np.int32)
        toks[0, :n] = r.prompt
        pre, _ = transformer.prefill(arch.cfg, weights,
                                     tokens=jnp.asarray(toks), max_len=64,
                                     return_all_logits=True)
        ref = _reference(weights, seqs[i], [n - 1])[0]
        got = np.asarray(pre[0, n - 1])
        scale = np.abs(ref).max()
        assert np.abs(got - ref).max() <= LOGIT_TOL * scale
        assert r.generated[0] == int(np.argmax(got))

    # decode: the lane's token at position `length`, its logits against the
    # reference's at that position
    compared = 0
    for logits, token, lengths, active, rid in steps:
        for lane in np.nonzero(active)[0]:
            i = rid[lane]
            pos = int(lengths[lane])
            assert token[lane] == seqs[i][pos]
            ref = _reference(weights, seqs[i], [pos])[0]
            scale = np.abs(ref).max()
            assert np.abs(logits[lane] - ref).max() <= LOGIT_TOL * scale
            compared += 1
    assert compared == 2 * (MAX_NEW - 1)


def test_bfloat16_program_misses_the_tolerance(weights):
    """The tolerance is tight enough that the same steps in bfloat16 fail
    it: the served tokens' logits then stray by about 2e-2 of their
    scale."""
    arch = _arch(dtype="bfloat16")
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), weights)
    _, reqs, steps = _serve(arch, params, use_kernel=False)
    worst = 0.0
    for logits, _token, lengths, active, rid in steps:
        for lane in np.nonzero(active)[0]:
            r = reqs[rid[lane]]
            seq = np.concatenate([r.prompt, r.generated]).astype(np.int32)
            ref = _reference(weights, seq, [int(lengths[lane])])[0]
            worst = max(worst, float(np.abs(logits[lane] - ref).max()
                                     / np.abs(ref).max()))
    assert worst > 10 * LOGIT_TOL


# ------------------------------------------------------- chunked attention

def _scores_shapes(jaxpr) -> list:
    """Every value's shape in ``jaxpr`` and the jaxprs it calls."""
    out = []
    for eqn in jaxpr.eqns:
        out += [tuple(v.aval.shape) for v in eqn.outvars
                if hasattr(v.aval, "shape")]
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out += _scores_shapes(sub)
    return out


@pytest.mark.parametrize("s,chunk", [(37, 8), (30, 12), (19, 5)])
def test_chunked_attention_equals_unchunked(weights, s, chunk):
    """At a length that is not a multiple of the chunk the chunked path
    pads the query rows and equals the one-shot scores; no (s, s) score
    array is built."""
    assert s % chunk
    lp = jax.tree_util.tree_map(lambda a: a[0], weights["layers"]["attn"])
    x = jax.random.normal(jax.random.PRNGKey(s), (2, s, MODEL["d_model"]))
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (2, s))
    one = _arch(attn_chunk=0).cfg
    chunked = _arch(attn_chunk=chunk).cfg
    assert query_chunk(one, s) == 0 and query_chunk(chunked, s) == chunk
    want = attention(lp, one, x, pos)
    got = attention(lp, chunked, x, pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    shapes = _scores_shapes(jax.make_jaxpr(
        functools.partial(attention, lp, chunked))(x, pos).jaxpr)
    assert not [sh for sh in shapes if sh[-2:] == (s, s)]
    assert [sh for sh in shapes if sh[-2:] == (chunk, s)]


# ------------------------------------------------------- rotary pairing

def _rope_interleaved(x, positions, theta=10000.0):
    """ChatGLM's rotary (modeling_chatglm ``apply_rotary_pos_emb``): on the
    first half of the head, pairs (2i, 2i + 1) rotate by position x
    theta^(-2i / rot)."""
    rot = x.shape[-1] // 2
    inv = 1.0 / theta ** (np.arange(0, rot, 2) / rot)
    ang = positions[..., None].astype(np.float64) * inv       # (B, S, rot/2)
    cos, sin = np.cos(ang)[:, :, None], np.sin(ang)[:, :, None]
    xr, rest = x[..., :rot], x[..., rot:]
    a, b = xr[..., 0::2], xr[..., 1::2]
    out = np.empty_like(xr)
    out[..., 0::2] = a * cos - b * sin
    out[..., 1::2] = b * cos + a * sin
    return np.concatenate([out, rest], -1)


def _pairing(hd: int) -> np.ndarray:
    """Channel order that takes ChatGLM's pairs (2i, 2i + 1) of the rotated
    half to the program's pairs (i, i + rot/2); the rest stay in place."""
    rot = hd // 2
    return np.concatenate([np.arange(0, rot, 2), np.arange(1, rot, 2),
                           np.arange(rot, hd)])


def test_half_rotary_is_chatglm_interleaved_under_a_channel_permutation():
    """Rotating ChatGLM's interleaved pairs and then permuting the channels
    equals permuting and then rotating the program's way (and the
    reference's): so permuting the rotated channels of wq, wk, bq and bk
    makes the two the same function, and their attention scores equal."""
    hd, h, kv = 16, 4, 2
    rng = np.random.default_rng(0)
    q = rng.standard_normal((2, 9, h, hd))
    k = rng.standard_normal((2, 9, kv, hd))
    pos = np.broadcast_to(np.arange(9)[None], (2, 9)) + np.array([[0], [40]])
    perm = _pairing(hd)
    glm_q, glm_k = _rope_interleaved(q, pos), _rope_interleaved(k, pos)
    got_q, got_k = apply_rope_half(jnp.asarray(q[..., perm], jnp.float32),
                                   jnp.asarray(k[..., perm], jnp.float32),
                                   jnp.asarray(pos), 10000.0)
    np.testing.assert_allclose(np.asarray(got_q), glm_q[..., perm], atol=1e-5)
    np.testing.assert_allclose(np.asarray(got_k), glm_k[..., perm], atol=1e-5)
    # the reference pairs channels as the program does
    m = {"rope": "half", "rope_theta": 10000.0}
    for b in range(2):
        ref_q = reference._rope(m, jnp.asarray(q[b][..., perm], jnp.float32),
                                jnp.asarray(pos[b]))
        np.testing.assert_allclose(np.asarray(ref_q), glm_q[b][..., perm],
                                   atol=1e-5)
    # the scores, query head j against kv head j // 2, are the same
    glm = np.einsum("bsjd,btjd->bjst", glm_q, np.repeat(glm_k, h // kv, 2))
    prog = np.einsum("bsjd,btjd->bjst", np.asarray(got_q),
                     np.repeat(np.asarray(got_k), h // kv, 2))
    np.testing.assert_allclose(prog, glm, atol=1e-4)
