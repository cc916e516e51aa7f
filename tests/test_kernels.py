"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode
(the kernel body executes on CPU exactly as it would on the TPU grid)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.batched_gather.kernel import batched_gather
from repro.kernels.batched_gather.ref import gather_ref
from repro.kernels.decode_attention.kernel import decode_attention_kernel
from repro.kernels.decode_attention.ref import decode_ref
from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.flash_attention.ref import attention_ref

KEY = jax.random.PRNGKey(0)


def _tol(dt):
    return (3e-2, 3e-2) if dt == jnp.bfloat16 else (2e-5, 2e-5)


@pytest.mark.parametrize("b,hq,hkv,s,d,bq,bk", [
    (1, 4, 2, 128, 64, 32, 32),
    (2, 8, 2, 256, 64, 64, 64),
    (1, 2, 1, 64, 128, 64, 16),
    (2, 4, 4, 96, 32, 32, 32),   # MHA, non-pow2 seq
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(b, hq, hkv, s, d, bq, bk, dtype, causal):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, hq, s, d), dtype)
    k = jax.random.normal(ks[1], (b, hkv, s, d), dtype)
    v = jax.random.normal(ks[2], (b, hkv, s, d), dtype)
    out = flash_attention(q, k, v, causal=causal, bq=bq, bk=bk, interpret=True)
    ref = attention_ref(q, k, v, causal=causal)
    rtol, atol = _tol(dtype)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), rtol=rtol, atol=atol)


@pytest.mark.parametrize("b,hq,hkv,t,d,bk", [
    (2, 4, 2, 128, 64, 32),
    (3, 8, 2, 256, 64, 64),
    (1, 16, 4, 512, 32, 128),
    (2, 4, 1, 64, 128, 32),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_sweep(b, hq, hkv, t, d, bk, dtype):
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (b, hq, d), dtype)
    k = jax.random.normal(ks[1], (b, t, hkv, d), dtype)
    v = jax.random.normal(ks[2], (b, t, hkv, d), dtype)
    lengths = jax.random.randint(ks[3], (b,), 1, t + 1)
    out = decode_attention_kernel(q, k, v, lengths, bk=bk, interpret=True)
    ref = decode_ref(q, k, v, lengths)
    rtol, atol = _tol(dtype)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), rtol=rtol, atol=atol)


def test_decode_attention_length_edge_cases():
    """length=1 and length=T (full cache)."""
    b, hq, hkv, t, d = 2, 4, 2, 64, 32
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, hq, d))
    k = jax.random.normal(ks[1], (b, t, hkv, d))
    v = jax.random.normal(ks[2], (b, t, hkv, d))
    for lengths in [jnp.array([1, 1]), jnp.array([t, t]), jnp.array([1, t])]:
        out = decode_attention_kernel(q, k, v, lengths, bk=16, interpret=True)
        ref = decode_ref(q, k, v, lengths)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("v,d,n,bn", [
    (64, 16, 32, 8), (128, 32, 64, 16), (100, 8, 40, 40), (256, 128, 128, 64),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int32])
def test_batched_gather_sweep(v, d, n, bn, dtype):
    if dtype == jnp.int32:
        table = jax.random.randint(KEY, (v, d), 0, 1000)
    else:
        table = jax.random.normal(KEY, (v, d), dtype)
    ids = jax.random.randint(KEY, (n,), 0, v)
    out = batched_gather(table, ids, bn=bn, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(gather_ref(table, ids)))


def test_gather_duplicate_and_boundary_ids():
    table = jax.random.normal(KEY, (32, 8))
    ids = jnp.array([0, 0, 31, 31, 5, 5, 0, 31])
    out = batched_gather(table, ids, bn=8, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(gather_ref(table, ids)))


@pytest.mark.parametrize("b,c,h,p,n", [
    (2, 8, 4, 16, 32), (1, 16, 2, 8, 8), (3, 4, 5, 32, 16), (1, 32, 1, 64, 64),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_scan_sweep(b, c, h, p, n, dtype):
    from repro.kernels.ssd_scan.kernel import ssd_scan
    from repro.kernels.ssd_scan.ref import ssd_scan_ref

    ks = jax.random.split(KEY, 2)
    states = jax.random.normal(ks[0], (b, c, h, p, n), dtype)
    decay = jax.nn.sigmoid(jax.random.normal(ks[1], (b, c, h))).astype(jnp.float32)
    prev, fin = ssd_scan(states, decay, interpret=True)
    rprev, rfin = ssd_scan_ref(states, decay)
    rtol, atol = _tol(dtype)
    np.testing.assert_allclose(np.asarray(prev, np.float32),
                               np.asarray(rprev, np.float32), rtol=rtol, atol=atol)
    np.testing.assert_allclose(np.asarray(fin), np.asarray(rfin),
                               rtol=rtol, atol=atol)


def test_ssd_scan_matches_model_ssd_chunked():
    """The kernel's semantics == the inter-chunk lax.scan inside
    models.ssm.ssd_chunked (state entering each chunk + final state)."""
    from repro.kernels.ssd_scan.ref import ssd_scan_ref

    b, c, h, p, n = 2, 6, 3, 8, 16
    ks = jax.random.split(KEY, 2)
    states = jax.random.normal(ks[0], (b, c, h, p, n))
    decay = jax.nn.sigmoid(jax.random.normal(ks[1], (b, c, h)))

    def model_scan(states, decay):
        s0 = jnp.zeros((b, h, p, n), jnp.float32)

        def step(carry, inp):
            st_c, dec_c = inp
            return carry * dec_c[:, :, None, None] + st_c, carry

        final, prev = jax.lax.scan(
            step, s0, (jnp.moveaxis(states, 1, 0), jnp.moveaxis(decay, 1, 0)))
        return jnp.moveaxis(prev, 0, 1), final

    p1, f1 = ssd_scan_ref(states, decay)
    p2, f2 = model_scan(states, decay)
    np.testing.assert_allclose(np.asarray(p1), np.asarray(p2), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(f1), np.asarray(f2), rtol=1e-6)


def test_ops_wrappers_fall_back_on_cpu():
    from repro.kernels.batched_gather.ops import gather_op
    from repro.kernels.decode_attention.ops import decode_op
    from repro.kernels.flash_attention.ops import attention_op

    q = jax.random.normal(KEY, (1, 4, 64, 32))
    k = jax.random.normal(KEY, (1, 2, 64, 32))
    v = jax.random.normal(KEY, (1, 2, 64, 32))
    out = attention_op(q, k, v, use_kernel=False)
    # jit vs eager: XLA CPU fuses softmax differently → small numeric drift
    np.testing.assert_allclose(np.asarray(out), np.asarray(attention_ref(q, k, v)),
                               rtol=2e-3, atol=2e-3)
    qd = jax.random.normal(KEY, (2, 4, 32))
    kd = jax.random.normal(KEY, (2, 64, 2, 32))
    vd = jax.random.normal(KEY, (2, 64, 2, 32))
    lens = jnp.array([10, 60])
    np.testing.assert_allclose(
        np.asarray(decode_op(qd, kd, vd, lens, use_kernel=False)),
        np.asarray(decode_ref(qd, kd, vd, lens)), rtol=2e-3, atol=2e-3)
    t = jax.random.normal(KEY, (100, 16))
    ids = jnp.arange(50) % 100
    np.testing.assert_array_equal(np.asarray(gather_op(t, ids, use_kernel=False)),
                                  np.asarray(gather_ref(t, ids)))

# --------------------------------------------------------------- registry

def test_registry_facade_exports():
    """`import repro.kernels` populates the registry and re-exports every
    public wrapper — the one entry point callers need."""
    import repro.kernels as K

    assert set(K.registry.names()) == {
        "batched_gather", "decode_attention", "flash_attention",
        "paged_decode_attention", "ssd_scan"}
    for name in K.__all__:
        assert getattr(K, name) is not None


@pytest.mark.parametrize("name", [
    "batched_gather", "decode_attention", "flash_attention",
    "paged_decode_attention", "ssd_scan"])
def test_registry_parity_sweep(name):
    """Registry-driven ref-vs-kernel parity: every registered op's sample
    agrees between its Pallas kernel (interpret mode) and its jnp oracle —
    registering an op automatically buys it this gate."""
    import repro.kernels as K

    op = K.registry.get(name)
    assert op.sample is not None, f"{name} registered without a parity sample"
    for seed in (0, 1):
        s = op.sample(jax.random.PRNGKey(seed))
        ref = op.ref(*s.args, **s.common)
        out = op.kernel(*s.args, **s.common, **s.kernel, interpret=True)
        for r, o in zip(jax.tree_util.tree_leaves(ref),
                        jax.tree_util.tree_leaves(out)):
            if s.tol is None:
                np.testing.assert_array_equal(np.asarray(o), np.asarray(r))
            else:
                np.testing.assert_allclose(
                    np.asarray(o, np.float32), np.asarray(r, np.float32),
                    rtol=s.tol[0], atol=s.tol[1])


def test_registry_dispatch_policy():
    """dispatch() falls back to the ref off-TPU without interpret, runs the
    kernel under interpret, and respects the supports gate."""
    from repro.kernels import registry
    from repro.kernels.batched_gather.ref import gather_ref

    table = jax.random.normal(KEY, (64, 16))
    ids = jax.random.randint(KEY, (24,), 0, 64)
    # 24 % min(16, 24) != 0 → supports rejects → ref even under interpret
    out = registry.dispatch("batched_gather", (table, ids),
                            kernel_kwargs={"bn": 16}, interpret=True)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(gather_ref(table, ids)))
    with pytest.raises(KeyError, match="unknown kernel op"):
        registry.get("nope")
    # conflicting re-registration is an error; identical one is a no-op
    op = registry.get("batched_gather")
    registry.register("batched_gather", ref=op.ref, kernel=op.kernel,
                      supports=op.supports, sample=op.sample)
    with pytest.raises(ValueError, match="already registered"):
        registry.register("batched_gather", ref=lambda *a: None,
                          kernel=lambda *a, **k: None)


@pytest.mark.parametrize("backend,use_kernel,interpret,want", [
    ("tpu", True, False, ("kernel", False)),
    ("tpu", True, True, ("kernel", False)),   # never interpreted on TPU
    ("tpu", False, True, ("ref", None)),
    ("cpu", True, True, ("kernel", True)),
    ("cpu", True, False, ("ref", None)),
])
def test_registry_dispatch_rule_by_backend(monkeypatch, backend, use_kernel,
                                           interpret, want):
    """On TPU dispatch always runs the compiled kernel unless
    use_kernel=False; off TPU the kernel runs only in interpret mode."""
    from repro.kernels import registry

    op = registry.KernelOp(
        "probe", ref=lambda x: ("ref", None),
        kernel=lambda x, interpret: ("kernel", interpret))
    monkeypatch.setitem(registry._OPS, "probe", op)
    monkeypatch.setattr(registry.jax, "default_backend", lambda: backend)
    assert registry.dispatch("probe", (0,), use_kernel=use_kernel,
                             interpret=interpret) == want


# --------------------------------------------------------- paged attention

@pytest.mark.parametrize("b,hq,hkv,np_,ps,d", [
    (2, 4, 2, 8, 16, 64),
    (1, 8, 2, 4, 32, 64),
    (3, 4, 4, 6, 8, 32),   # MHA, non-pow2 page count
    (2, 16, 16, 3, 16, 128),  # OLMo-1B heads and head_dim
    (2, 32, 8, 3, 16, 128),   # GQA at head_dim 128
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_decode_attention_sweep(b, hq, hkv, np_, ps, d, dtype):
    from repro.kernels.paged_attention.kernel import paged_decode_attention_kernel
    from repro.kernels.paged_attention.ref import paged_decode_ref

    n_pages = b * np_ + 1
    ks = jax.random.split(KEY, 5)
    q = jax.random.normal(ks[0], (b, hq, d), dtype)
    k_pages = jax.random.normal(ks[1], (n_pages, ps, hkv, d), dtype)
    v_pages = jax.random.normal(ks[2], (n_pages, ps, hkv, d), dtype)
    tables = jax.random.permutation(ks[3], jnp.arange(1, n_pages)
                                    ).reshape(b, np_).astype(jnp.int32)
    lengths = jax.random.randint(ks[4], (b,), 1, np_ * ps + 1)
    out = paged_decode_attention_kernel(q, k_pages, v_pages, tables, lengths,
                                        interpret=True)
    ref = paged_decode_ref(q, k_pages, v_pages, tables, lengths)
    rtol, atol = _tol(dtype)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), rtol=rtol, atol=atol)


def test_paged_decode_matches_dense_decode():
    """A paged cache whose tables are a permutation of a dense cache's
    pages attends identically to the dense split-KV kernel — paging is a
    layout change, not a numeric one."""
    b, hq, hkv, t, d, ps = 2, 4, 2, 128, 64, 16
    np_ = t // ps
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (b, hq, d))
    k = jax.random.normal(ks[1], (b, t, hkv, d))
    v = jax.random.normal(ks[2], (b, t, hkv, d))
    lengths = jnp.array([37, 128])
    # scatter the dense rows into a shuffled page pool
    perm = np.asarray(jax.random.permutation(ks[3], np.arange(b * np_)))
    k_pages = jnp.reshape(k, (b * np_, ps, hkv, d))[jnp.asarray(perm)]
    v_pages = jnp.reshape(v, (b * np_, ps, hkv, d))[jnp.asarray(perm)]
    inv = np.empty_like(perm)
    inv[perm] = np.arange(b * np_)
    tables = jnp.asarray(inv.reshape(b, np_), jnp.int32)
    from repro.kernels.paged_attention.kernel import paged_decode_attention_kernel

    paged = paged_decode_attention_kernel(q, k_pages, v_pages, tables, lengths,
                                          interpret=True)
    dense = decode_attention_kernel(q, k, v, lengths, bk=ps, interpret=True)
    np.testing.assert_allclose(np.asarray(paged), np.asarray(dense),
                               rtol=2e-5, atol=2e-5)


def test_paged_decode_padded_table_slots_unread():
    """Pages past ceil(length/ps) may alias ANY page (here: page 0 vs a
    poison page) without changing the output — the masking guarantee
    page-granular spill/restore relies on."""
    from repro.kernels.paged_attention.kernel import paged_decode_attention_kernel

    b, hq, hkv, np_, ps, d = 1, 4, 2, 4, 16, 32
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, hq, d))
    k_pages = jax.random.normal(ks[1], (np_ + 2, ps, hkv, d))
    v_pages = jax.random.normal(ks[2], (np_ + 2, ps, hkv, d))
    poison = np_ + 1
    k_pages = k_pages.at[poison].set(1e9)
    v_pages = v_pages.at[poison].set(1e9)
    lengths = jnp.array([2 * ps - 3])  # two valid pages
    t_pad0 = jnp.array([[1, 2, 0, 0]], jnp.int32)
    t_poison = jnp.array([[1, 2, poison, poison]], jnp.int32)
    out0 = paged_decode_attention_kernel(q, k_pages, v_pages, t_pad0, lengths,
                                         interpret=True)
    out1 = paged_decode_attention_kernel(q, k_pages, v_pages, t_poison,
                                         lengths, interpret=True)
    np.testing.assert_array_equal(np.asarray(out0), np.asarray(out1))
