"""The prefill ladder: each templated prompt runs as its own ``(1, rung)``
program (``InferenceEngine.prefill_dispatch``).

In float32 on tiny OLMo-shaped and ChatGLM-shaped (G = 16, chunked
attention) configurations, the ladder serves what one padded batch of the
same prompts serves; and once a template has dispatched, prompts of every
length in batches of every size dispatch and commit with no compile.
"""
from __future__ import annotations

import dataclasses

import jax
import numpy as np
import pytest

from repro.models.registry import get_arch
from repro.serving import tracing
from repro.serving.engine import InferenceEngine
from repro.serving.paged_kv import PagedInferenceEngine
from repro.serving.request import Request

# 300 and 40 tokens take the 512 and 256 rungs, 513 the capped 600 rung,
# which is also the padded batch's prompt width.
LENGTHS = (300, 40, 513)
MAX_PROMPT = 600
DECODE_STEPS = 8


def _arch(name: str):
    arch = get_arch(name)
    cfg = dataclasses.replace(arch.cfg.reduced(), param_dtype="float32",
                              compute_dtype="float32")
    if name == "chatglm3-6b":
        # ChatGLM's kinds at CPU widths, as tests/test_chatglm.py has them
        cfg = dataclasses.replace(cfg, n_layers=2, d_model=64, n_heads=32,
                                  n_kv_heads=2, head_dim=16, d_ff=128,
                                  vocab_size=256, attn_chunk=12)
    return dataclasses.replace(arch, cfg=cfg)


def _requests(lengths, rid0=0, max_new=DECODE_STEPS + 1):
    rng = np.random.default_rng(rid0 + 3)
    return [Request(rid=rid0 + i,
                    prompt=rng.integers(1, 200, size=n).astype(np.int32),
                    max_new_tokens=max_new)
            for i, n in enumerate(lengths)]


def _lane_kv(eng, r) -> list:
    """The committed KV of ``r``'s prompt rows, gathered from its pages."""
    pages = list(eng.pool.pages(r.lane))
    return [np.asarray(a)[:, pages].reshape(a.shape[0], -1, *a.shape[3:])
            [:, : len(r.prompt)] for a in jax.tree_util.tree_leaves(eng.cache)]


def _serve(arch, params, template):
    eng = PagedInferenceEngine(arch, params, n_lanes=4,
                               max_prompt_len=MAX_PROMPT, max_len=640,
                               page_size=16, use_kernel=False)
    reqs = _requests(LENGTHS)
    staged = eng.prefill_dispatch(reqs, template)
    eng.commit_prefill(staged)
    kv = [_lane_kv(eng, r) for r in reqs]
    by_lane = {r.lane: r for r in reqs}
    for _ in range(DECODE_STEPS):
        for lane, tok in eng.decode_tick().items():
            by_lane[lane].generated.append(tok)
    return eng, staged, reqs, kv


@pytest.mark.parametrize("name", ["olmo-1b", "chatglm3-6b"])
def test_ladder_serves_what_the_padded_batch_serves_in_float32(name):
    """Three prompts as three rung programs, against the same prompts in
    the one (4, 600) padded program the template path pinned before the
    ladder: the first tokens and the greedy tokens of 8 decode steps are
    identical.  The committed KV pages are bit-identical where the rung is
    the batch's width (the prompt's row alone in a batch of one); where
    it is narrower, only the order of summation over the padded key axis
    differs, about 2e-6 of the pages' scale."""
    arch = _arch(name)
    params = arch.init(jax.random.PRNGKey(0))
    lad, lad_staged, lad_reqs, lad_kv = _serve(arch, params, "t")
    pin, pin_staged, pin_reqs, pin_kv = _serve(arch, params, None)
    assert [p.shape for p in lad_staged.parts] == [(1, 512), (1, 256),
                                                   (1, 600)]
    assert pin_staged.shape == (4, 600) and not pin_staged.parts
    assert lad.prefill_rung_programs == {256: 1, 512: 1, 600: 1}
    for a, b in zip(lad_reqs, pin_reqs):
        assert len(a.generated) == DECODE_STEPS + 1
        assert a.generated == b.generated
    for i, n in enumerate(LENGTHS):
        for x, y in zip(lad_kv[i], pin_kv[i]):
            if lad._rung(n) == pin_staged.shape[1]:
                np.testing.assert_array_equal(x, y)
            else:
                assert np.abs(x - y).max() <= 1e-5 * np.abs(y).max()


@pytest.mark.parametrize("paged", [True, False], ids=["paged", "dense"])
def test_no_compile_after_a_templates_first_dispatch(paged):
    """After a template's first dispatch and commit, prompts of every
    length up to ``max_prompt_len`` (every rung and every page count), in
    batches of every size up to ``n_lanes``, dispatch and commit with no
    backend compile."""
    arch = _arch("olmo-1b")
    params = arch.init(jax.random.PRNGKey(0))
    kw = dict(n_lanes=4, max_prompt_len=300, max_len=320)
    eng = (PagedInferenceEngine(arch, params, page_size=16, use_kernel=False,
                                **kw) if paged
           else InferenceEngine(arch, params, **kw))
    assert eng.prefill_rungs() == [256, 300]
    (first,) = _requests([7], rid0=10**6, max_new=2)
    eng.admit([first], template="t")
    eng.retire(first.lane)
    log = tracing.CompileLog()
    lengths = list(range(1, 301))
    rid, k = 0, 1
    while lengths:
        batch, lengths = lengths[:k], lengths[k:]
        reqs = _requests(batch, rid0=rid, max_new=2)
        eng.admit(reqs, template="t")
        for r in reqs:
            assert len(r.generated) == 1
            eng.retire(r.lane)
        rid += len(reqs)
        k = k % kw["n_lanes"] + 1
    assert rid == 300
    assert log.compiles == 0, log.line()
    assert sum(eng.prefill_rung_programs.values()) == 301
