"""Chip smoke: serve OLMo-1B at its published widths on one TPU.

    python chip_smoke.py

Drives the serving main path once, in this one process, through the entry
point a user calls (:func:`repro.launch.serve.serve_requests`): admission
under the scheduler, the :class:`PagedInferenceEngine`, and the Pallas
``paged_decode_attention`` kernel over the shared page pool.  The weights
are random, drawn from ``SEED``.  Phases, each of which raises on failure:

1. device — exit non-zero with a one-line reason unless JAX's default
   backend is a TPU (there is no CPU fallback);
2. compile cache — :func:`repro.launch.compile_cache.setup_compile_cache`;
3. serve — 16 requests, prompts of 32-256 tokens, 32 new tokens each;
4. kernel — the engine's compiled paged decode program must contain a
   ``tpu_custom_call`` (the kernel, not the jnp reference);
5. parity — on live engine state: the attention op, kernel vs reference,
   within the kernel tests' bf16 tolerance; one paged decode step's
   logits, kernel vs reference, within ``LOGIT_TOL``;
6. reference pass — the same requests served with ``use_kernel=False``;
   every request's first token must match.

Wall times printed are smoke times (compilation included), not metrics.
The last line of stdout is the JSON result; it is printed only when every
phase passed.
"""
from __future__ import annotations

import functools
import json
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

ARCH = "olmo-1b"
SEED = 0
N_REQUESTS = 16
PROMPT_LEN = (32, 256)      # inclusive range of prompt lengths
MAX_NEW = 32
LANES = 8
MAX_LEN = 1024
MAX_PROMPT_LEN = 256
PAGE_SIZE = 16
# Attention op, kernel vs reference, bf16: the kernel tests' tolerance.
ATTN_TOL = (3e-2, 3e-2)     # (rtol, atol)
# Logits after 16 bf16 layers: max |kernel - ref| over all active lanes,
# as a fraction of max |ref|.
LOGIT_TOL = 5e-2


def fail(reason: str) -> None:
    print(f"chip_smoke: FAILED: {reason}", file=sys.stderr)
    sys.exit(1)


class CompileLog:
    """Backend compile seconds and persistent-cache hits/misses, from
    JAX's monitoring events."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.compiles = 0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1

    def _event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def line(self) -> str:
        return (f"{self.compiles} backend compiles, {self.seconds:.1f}s; "
                f"persistent cache {self.hits} hits, {self.misses} misses")


def make_requests(prompts, max_new):
    from repro.serving.request import Request

    return [Request(rid=i, prompt=p, max_new_tokens=max_new)
            for i, p in enumerate(prompts)]


def serve_phase(arch, params, prompts, *, use_kernel):
    """Serve ``prompts`` through the main path; check every request came
    back with ``MAX_NEW`` in-vocabulary tokens."""
    from repro.launch.serve import serve_requests

    t0 = time.perf_counter()
    eng, sched, done = serve_requests(
        arch, params, make_requests(prompts, MAX_NEW), lanes=LANES,
        max_len=MAX_LEN, max_prompt_len=MAX_PROMPT_LEN, page_size=PAGE_SIZE,
        use_kernel=use_kernel)
    dt = time.perf_counter() - t0
    if len(done) != len(prompts):
        raise AssertionError(f"{len(done)} of {len(prompts)} requests finished")
    for r in done:
        g = r.generated
        if len(g) != MAX_NEW or min(g) < 0 or max(g) >= arch.cfg.vocab_size:
            raise AssertionError(f"request {r.rid}: bad tokens {g[:8]}...")
    toks = sum(len(r.generated) for r in done)
    print(f"serve (use_kernel={use_kernel}): {len(done)} requests, {toks} "
          f"tokens, {eng.decode_steps} decode ticks, {eng.prefill_calls} "
          f"prefill batches; smoke time {dt:.1f}s (compilation included)")
    return eng, {r.rid: list(r.generated) for r in done}


def kernel_phase(eng) -> int:
    """Count ``tpu_custom_call`` in the engine's compiled paged decode
    program; the Pallas kernel must be in it."""
    import jax.numpy as jnp

    tables = jnp.zeros((eng.n_lanes, eng.pages_per_lane), jnp.int32)
    compiled = eng._paged_decode.lower(
        eng.params, eng.last_token, eng.cache, eng.lengths, tables,
        jnp.asarray(eng.active), jnp.asarray(eng.lane_temps),
        jnp.asarray(eng.lane_seeds)).compile()
    n = compiled.as_text().count("tpu_custom_call")
    print(f"paged decode program: {n} tpu_custom_call")
    if n < 1:
        raise AssertionError("the paged decode program holds no Pallas kernel")
    return n


def parity_phase(eng, arch, params, prompts) -> dict:
    """Kernel vs reference on live state: admit ``LANES`` prompts into the
    drained engine, then compare the attention op on layer 0's pages and
    one full paged decode step's logits."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import registry
    from repro.models.paged_decode import paged_decode_step

    reqs = make_requests(prompts[:LANES], MAX_NEW)
    eng.admit(reqs)
    for _ in range(3):  # a few ticks so the pages hold decode-written rows
        eng.decode_tick()
    cfg = arch.cfg

    view = eng.paged_view()
    q = jax.random.normal(jax.random.PRNGKey(SEED + 1),
                          (len(view["lanes"]), cfg.n_heads, cfg.hd),
                          cfg.cdtype)
    args = (q, view["k_pages"], view["v_pages"], view["block_tables"],
            view["lengths"])

    @functools.partial(jax.jit, static_argnames="use_kernel")
    def attn(args, use_kernel):
        return registry.dispatch("paged_decode_attention", args,
                                 use_kernel=use_kernel)

    a_k = np.asarray(attn(args, True), np.float32)
    a_r = np.asarray(attn(args, False), np.float32)
    attn_err = float(np.max(np.abs(a_k - a_r)))
    np.testing.assert_allclose(a_k, a_r, rtol=ATTN_TOL[0], atol=ATTN_TOL[1])

    tables = jnp.asarray(np.stack([
        eng.pool.block_table(lane, eng.pages_per_lane)
        for lane in range(eng.n_lanes)]))
    active = jnp.asarray(eng.active)

    @functools.partial(jax.jit, static_argnames="use_kernel")
    def step(params, token, cache, lengths, use_kernel):
        return paged_decode_step(cfg, params, token, cache, tables, lengths,
                                 active, use_kernel=use_kernel)[0]

    lk = np.asarray(step(params, eng.last_token, eng.cache, eng.lengths,
                         True), np.float32)
    lr = np.asarray(step(params, eng.last_token, eng.cache, eng.lengths,
                         False), np.float32)
    on = np.asarray(eng.active)
    lk, lr = lk[on], lr[on]
    if not (np.isfinite(lk).all() and np.isfinite(lr).all()):
        raise AssertionError("non-finite logits")
    err = float(np.max(np.abs(lk - lr)))
    scale = float(np.max(np.abs(lr)))
    differ = lk.argmax(-1) != lr.argmax(-1)
    top2 = np.sort(lr, -1)[:, -2:]
    gaps = ", ".join(f"{g:.3g}" for g in (top2[:, 1] - top2[:, 0])[differ])
    print(f"parity: attention max|kernel-ref| {attn_err:.3g} (rtol/atol "
          f"{ATTN_TOL[0]}/{ATTN_TOL[1]}); decode logits max|kernel-ref| "
          f"{err:.3g} vs max|ref| {scale:.3g} (limit {LOGIT_TOL} x max|ref|), "
          f"argmax equal on {int((~differ).sum())}/{int(on.sum())} lanes"
          + (f" (reference top-2 logit gap where it differs: {gaps})"
             if gaps else ""))
    if err > LOGIT_TOL * scale:
        raise AssertionError(
            f"decode logits differ: {err:.3g} > {LOGIT_TOL} x {scale:.3g}")
    return {"attn_err": attn_err, "logit_err": err, "logit_scale": scale}


def main() -> None:
    if not (SRC / "repro").is_dir():
        fail(f"the repo's sources are not next to this script ({SRC})")
    sys.path.insert(0, str(SRC))
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        fail(f"needs a TPU, but JAX's default backend is {backend!r}")
    import numpy as np

    from repro.launch.compile_cache import setup_compile_cache
    from repro.models.registry import get_arch

    cache = setup_compile_cache()
    print(f"compile cache: {jax.config.jax_compilation_cache_dir} "
          f"({'set here' if cache else 'from JAX_COMPILATION_CACHE_DIR'})")
    log = CompileLog()
    dev = jax.devices()[0]
    t_start = time.perf_counter()

    arch = get_arch(ARCH)
    cfg = arch.cfg
    print(f"model {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"{cfg.n_heads} heads / {cfg.n_kv_heads} kv heads, head_dim "
          f"{cfg.hd}, {cfg.param_dtype}; {LANES} lanes x {MAX_LEN} tokens, "
          f"page {PAGE_SIZE}")
    params = jax.jit(arch.init)(jax.random.PRNGKey(SEED))
    rng = np.random.default_rng(SEED)
    prompts = [rng.integers(1, cfg.vocab_size,
                            size=int(rng.integers(PROMPT_LEN[0],
                                                  PROMPT_LEN[1] + 1))
                            ).astype(np.int32) for _ in range(N_REQUESTS)]

    eng, out_k = serve_phase(arch, params, prompts, use_kernel=True)
    n_calls = kernel_phase(eng)
    parity_phase(eng, arch, params, prompts)
    del eng

    out_r = serve_phase(arch, params, prompts, use_kernel=False)[1]
    first_bad = [rid for rid in out_k if out_k[rid][0] != out_r[rid][0]]
    same = sum(a == b for rid in out_k for a, b in zip(out_k[rid], out_r[rid]))
    total = sum(len(g) for g in out_k.values())
    print(f"reference pass: first tokens equal on "
          f"{len(out_k) - len(first_bad)}/{len(out_k)} requests; "
          f"{same}/{total} tokens equal position by position")
    if first_bad:
        raise AssertionError(f"first tokens differ for requests {first_bad}")

    stats = dev.memory_stats() or {}
    print(f"peak_bytes_in_use {stats.get('peak_bytes_in_use', 'not reported')}"
          f"; {log.line()}; custom calls {n_calls}; smoke time "
          f"{time.perf_counter() - t_start:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
