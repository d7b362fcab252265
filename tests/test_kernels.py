"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs pure-jnp oracle."""
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                                    # CPU container: shim
    from _hypothesis_compat import given, settings, st

import jax
import jax.numpy as jnp

from repro.core import TileConfig
from repro.kernels import flash_attention, matmul, select_attention_blocks
from repro.kernels import ref

RNG = np.random.default_rng(0)


def _mm_case(M, N, K, dt, **kw):
    a = jnp.asarray(RNG.standard_normal((M, K)), dtype=dt)
    b = jnp.asarray(RNG.standard_normal((K, N)), dtype=dt)
    want = np.asarray(ref.matmul_ref(a, b, out_dtype=jnp.float32))
    got = np.asarray(matmul(a, b, out_dtype=jnp.float32,
                            backend="pallas_interpret", **kw))
    rtol = 1e-5 if dt == jnp.float32 else 3e-2
    atol = (1e-4 if dt == jnp.float32 else 0.3) * np.sqrt(K)
    np.testing.assert_allclose(got, want, rtol=rtol, atol=atol)


@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [
    (128, 128, 128),       # single block
    (256, 512, 384),       # multi-block, ragged K
    (100, 300, 77),        # fully unaligned (padding path)
    (512, 256, 1024),      # k-major
    (1, 128, 128),         # degenerate M
    (640, 256, 256),       # non-pow2 M
])
def test_matmul_vs_ref(shape, dt):
    _mm_case(*shape, dt)


def test_matmul_selected_config_paths():
    """The analytically selected config must be numerically equivalent."""
    for (M, N, K) in [(384, 640, 512), (2048, 256, 128), (64, 2048, 2048)]:
        _mm_case(M, N, K, jnp.bfloat16)


def test_matmul_split_k():
    _mm_case(64, 128, 2048, jnp.bfloat16,
             config=TileConfig(bm=64, bn=128, bk=256, split_k=4))


def test_matmul_grouped_order():
    _mm_case(512, 256, 256, jnp.bfloat16,
             config=TileConfig(bm=128, bn=128, bk=256, group_m=4))


def test_matmul_batched_leading_dims():
    a = jnp.asarray(RNG.standard_normal((2, 3, 64, 128)), dtype=jnp.float32)
    b = jnp.asarray(RNG.standard_normal((128, 96)), dtype=jnp.float32)
    got = np.asarray(matmul(a, b, out_dtype=jnp.float32,
                            backend="pallas_interpret"))
    want = np.asarray(ref.matmul_ref(a.reshape(-1, 128), b)
                      ).reshape(2, 3, 64, 96)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-3)


@settings(max_examples=10, deadline=None)
@given(
    M=st.integers(1, 4).map(lambda k: k * 64 + 32),
    N=st.integers(1, 3).map(lambda k: k * 128),
    K=st.integers(1, 3).map(lambda k: k * 128 - 5),
)
def test_matmul_property_random_shapes(M, N, K):
    _mm_case(M, N, K, jnp.float32)


# ---------------------------------------------------------------------------
# Fused epilogue: interpret-mode kernel vs the pure-jnp oracle.
# ---------------------------------------------------------------------------

from repro.core import Epilogue                              # noqa: E402
from repro.kernels import expert_matmul                      # noqa: E402
from repro.kernels.ref import apply_epilogue_ref             # noqa: E402

EPILOGUES = [
    Epilogue(bias=True),
    Epilogue(activation="gelu"),
    Epilogue(activation="silu"),
    Epilogue(activation="swiglu_gate"),
    Epilogue(bias=True, activation="gelu"),
    Epilogue(residual=True),
    Epilogue(bias=True, activation="swiglu_gate", residual=True),
]


def _ep_operands(ep, M, N, dt):
    kw = {}
    if ep.bias:
        kw["bias"] = jnp.asarray(RNG.standard_normal(N), dtype=dt)
    if ep.activation == "swiglu_gate":
        kw["gate"] = jnp.asarray(RNG.standard_normal((M, N)), dtype=dt)
    if ep.residual:
        kw["residual"] = jnp.asarray(RNG.standard_normal((M, N)), dtype=dt)
    return kw


@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [
    (128, 128, 128),       # aligned
    (100, 300, 77),        # fully ragged (padding path)
    (8, 256, 512),         # skinny M
])
@pytest.mark.parametrize("ep", EPILOGUES, ids=str)
def test_matmul_epilogue_vs_ref(shape, dt, ep):
    M, N, K = shape
    a = jnp.asarray(RNG.standard_normal((M, K)), dtype=dt)
    b = jnp.asarray(RNG.standard_normal((K, N)), dtype=dt)
    kw = _ep_operands(ep, M, N, dt)
    acc = jnp.matmul(a.astype(jnp.float32), b.astype(jnp.float32))
    want = np.asarray(apply_epilogue_ref(acc, ep, **kw))
    got = np.asarray(matmul(a, b, out_dtype=jnp.float32, epilogue=ep,
                            backend="pallas_interpret", **kw))
    rtol = 1e-5 if dt == jnp.float32 else 3e-2
    atol = (1e-4 if dt == jnp.float32 else 0.3) * np.sqrt(K)
    np.testing.assert_allclose(got, want, rtol=rtol, atol=atol)


def _all_eqns(jaxpr):
    """Every equation of a jaxpr, nested ones included (the kernel launch
    sits inside its no-VJP wrapper's sub-jaxpr)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _all_eqns(sub)


@pytest.mark.parametrize("ep", [Epilogue(), Epilogue(activation="gelu"),
                                Epilogue(activation="swiglu_gate")], ids=str)
def test_matmul_split_k_in_kernel(ep):
    """Split-K fuses into ONE pallas_call: no (sk, M, N) HBM partials, no
    combine reduction, epilogue still applied at the single flush."""
    M, N, K = 64, 128, 2048
    cfg = TileConfig(bm=64, bn=128, bk=256, split_k=4)
    a = jnp.asarray(RNG.standard_normal((M, K)), dtype=jnp.float32)
    b = jnp.asarray(RNG.standard_normal((K, N)), dtype=jnp.float32)
    kw = _ep_operands(ep, M, N, jnp.float32)

    fn = lambda a, b: matmul(a, b, out_dtype=jnp.float32, config=cfg,
                             epilogue=ep, backend="pallas_interpret", **kw)
    eqns = list(_all_eqns(jax.make_jaxpr(fn)(a, b).jaxpr))
    calls = [e for e in eqns if e.primitive.name == "pallas_call"]
    assert len(calls) == 1
    sk_shape = (cfg.split_k, M, N)
    for eqn in eqns:
        for v in eqn.outvars:
            assert tuple(getattr(v.aval, "shape", ())) != sk_shape

    acc = jnp.matmul(a, b)
    want = np.asarray(apply_epilogue_ref(acc, ep, **kw))
    np.testing.assert_allclose(np.asarray(fn(a, b)), want,
                               rtol=1e-5, atol=1e-4 * np.sqrt(K))


@pytest.mark.parametrize("backend", ["reference", "pallas_interpret"])
def test_expert_matmul_grouped(backend):
    E, C, D, F = 4, 24, 64, 96
    x = jnp.asarray(RNG.standard_normal((E, C, D)), dtype=jnp.float32)
    wg = jnp.asarray(RNG.standard_normal((E, D, F)), dtype=jnp.float32)
    wu = jnp.asarray(RNG.standard_normal((E, D, F)), dtype=jnp.float32)
    u = expert_matmul(x, wu, backend=backend)
    got = np.asarray(expert_matmul(x, wg, epilogue="swiglu_gate", gate=u,
                                   backend=backend))
    g = jnp.einsum("ecd,edf->ecf", x, wg)
    want = np.asarray(jax.nn.silu(g) * jnp.einsum("ecd,edf->ecf", x, wu))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-3)


def test_matmul_out_dtype_selection_regression():
    """ops.matmul must hand the TRUE out_dtype to the selector: the seed
    inverted the conditional and priced every non-f32 output as f32
    (mis-modeling bf16 epilogue write bytes)."""
    from repro.core import clear_selection_cache
    from repro.core import selector as selector_mod
    clear_selection_cache()
    a = jnp.asarray(RNG.standard_normal((256, 256)), dtype=jnp.bfloat16)
    b = jnp.asarray(RNG.standard_normal((256, 256)), dtype=jnp.bfloat16)
    matmul(a, b, out_dtype=jnp.bfloat16, backend="pallas_interpret")
    out_dtypes = {s.problem.out_dtype for s in selector_mod._CACHE.values()}
    assert out_dtypes == {"bfloat16"}
    clear_selection_cache()
    matmul(a, b, out_dtype=jnp.float32, backend="pallas_interpret")
    out_dtypes = {s.problem.out_dtype for s in selector_mod._CACHE.values()}
    assert out_dtypes == {"float32"}


# ---------------------------------------------------------------------------
# Flash attention.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("cfg", [
    (1, 2, 2, 128, 128, 64),     # MHA
    (2, 4, 2, 256, 256, 64),     # GQA 2:1
    (1, 8, 2, 100, 300, 128),    # ragged seq (padding/mask path)
    (1, 2, 1, 384, 384, 128),    # GQA 2:1 deep
])
def test_flash_attention_vs_ref(cfg, causal):
    B, H, Hkv, Sq, Skv, d = cfg
    q = jnp.asarray(RNG.standard_normal((B, H, Sq, d)), dtype=jnp.float32)
    k = jnp.asarray(RNG.standard_normal((B, Hkv, Skv, d)), dtype=jnp.float32)
    v = jnp.asarray(RNG.standard_normal((B, Hkv, Skv, d)), dtype=jnp.float32)
    want = np.asarray(ref.attention_ref(q, k, v, causal=causal))
    got = np.asarray(flash_attention(q, k, v, causal=causal,
                                     backend="pallas_interpret",
                                     blocks=(128, 128)))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=2e-5)


def test_flash_attention_selected_blocks():
    bq, bkv = select_attention_blocks(4096, 4096, 128)
    assert bq >= 128 and bkv >= 128
    # selected blocks stay inside the VMEM budget by construction;
    # check determinism
    assert (bq, bkv) == select_attention_blocks(4096, 4096, 128)


def test_flash_attention_bf16():
    q = jnp.asarray(RNG.standard_normal((1, 4, 256, 64)), dtype=jnp.bfloat16)
    k = jnp.asarray(RNG.standard_normal((1, 2, 256, 64)), dtype=jnp.bfloat16)
    v = jnp.asarray(RNG.standard_normal((1, 2, 256, 64)), dtype=jnp.bfloat16)
    want = np.asarray(ref.attention_ref(q, k, v, causal=True)
                      ).astype(np.float32)
    got = np.asarray(flash_attention(q, k, v, causal=True,
                                     backend="pallas_interpret",
                                     blocks=(128, 128))).astype(np.float32)
    np.testing.assert_allclose(got, want, rtol=5e-2, atol=5e-2)


# ---------------------------------------------------------------------------
# jax-native chunked attention (the GSPMD/dry-run path) vs the same oracle.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("window", [0, 64])
@pytest.mark.parametrize("causal", [True, False])
def test_chunked_attention_vs_ref(causal, window):
    from repro.nn.attention import chunked_attention
    if window and not causal:
        pytest.skip("sliding window implies causal")
    B, H, Hkv, S, d = 2, 4, 2, 200, 32
    q = jnp.asarray(RNG.standard_normal((B, H, S, d)), dtype=jnp.float32)
    k = jnp.asarray(RNG.standard_normal((B, Hkv, S, d)), dtype=jnp.float32)
    v = jnp.asarray(RNG.standard_normal((B, Hkv, S, d)), dtype=jnp.float32)
    got = np.asarray(chunked_attention(q, k, v, causal=causal,
                                       sliding_window=window,
                                       chunk_q=64, chunk_k=64))
    qf, kf, vf = (x.astype(jnp.float32) for x in (q, k, v))
    kf = jnp.repeat(kf, 2, axis=1)
    vf = jnp.repeat(vf, 2, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", qf, kf) * (d ** -0.5)
    mask = jnp.ones((S, S), bool)
    if causal:
        mask = jnp.tril(mask)
    if window:
        iq, ik = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
        mask = mask & (iq - ik < window)
    s = jnp.where(mask, s, -jnp.inf)
    want = np.asarray(jnp.einsum("bhqk,bhkd->bhqd",
                                 jax.nn.softmax(s, axis=-1), vf))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def _decode_ref(q, k, v, pos, window):
    """numpy float32 decode attention: K/V repeated to H heads, then a
    masked softmax over each row's own prefix."""
    q, k, v = (np.asarray(x, dtype=np.float32) for x in (q, k, v))
    B, H, _, d = q.shape
    S = k.shape[2]
    group = H // k.shape[1]
    k, v = np.repeat(k, group, axis=1), np.repeat(v, group, axis=1)
    pos = np.broadcast_to(np.asarray(pos), (B,))[:, None]
    k_pos = np.arange(S)[None, :]
    mask = k_pos <= pos
    if window:
        mask &= pos - k_pos < window
    s = np.einsum("bhd,bhkd->bhk", q[:, :, 0], k) * d ** -0.5
    s = np.where(mask[:, None], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bhk,bhkd->bhd", p, v)[:, :, None]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("window", [0, 16])
@pytest.mark.parametrize("ragged", [False, True],
                         ids=["scalar_pos", "ragged_pos"])
@pytest.mark.parametrize("group", [1, 3, 6])
def test_decode_attention_vs_ref(group, ragged, window, dtype):
    """Grouped-query decode over the un-repeated cache against an
    independent f32 reference; bf16 allows for q, p and the output
    rounded to bf16."""
    from repro.nn.attention import decode_attention
    B, Hkv, S, d = 3, 2, 64, 32
    H = Hkv * group
    dt = jnp.dtype(dtype)
    q, k, v = (jnp.asarray(RNG.standard_normal(shape), dtype=dt)
               for shape in ((B, H, 1, d), (B, Hkv, S, d), (B, Hkv, S, d)))
    pos = (jnp.asarray([S - 1, 5, 37], jnp.int32) if ragged
           else jnp.int32(S - 9))
    got = decode_attention(q, k, v, pos=pos, sliding_window=window)
    assert got.shape == q.shape and got.dtype == dt
    want = _decode_ref(q, k, v, pos, window)
    tol = 1e-5 if dtype == "float32" else 1e-2
    np.testing.assert_allclose(np.asarray(got, np.float32), want,
                               rtol=tol, atol=tol)


def test_decode_attention_matches_prefix():
    from repro.nn.attention import chunked_attention, decode_attention
    B, H, Hkv, S, d = 1, 4, 2, 64, 32
    q = jnp.asarray(RNG.standard_normal((B, H, S, d)), dtype=jnp.float32)
    k = jnp.asarray(RNG.standard_normal((B, Hkv, S, d)), dtype=jnp.float32)
    v = jnp.asarray(RNG.standard_normal((B, Hkv, S, d)), dtype=jnp.float32)
    full = np.asarray(chunked_attention(q, k, v, causal=True,
                                        chunk_q=32, chunk_k=32))
    # decode for the last position must match the full causal row
    out = np.asarray(decode_attention(q[:, :, -1:, :], k, v,
                                      pos=jnp.int32(S - 1)))
    np.testing.assert_allclose(out[:, :, 0], full[:, :, -1],
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("op", ["matmul", "flash_attention"])
def test_grad_through_pallas_kernel_raises(op):
    """The kernels have no VJP: differentiating one fails loudly instead
    of the launch ladder quietly answering with the reference GEMM."""
    x = jnp.asarray(RNG.standard_normal((2, 128, 128)), jnp.float32)
    w = jnp.asarray(RNG.standard_normal((128, 128)), jnp.float32)
    if op == "matmul":
        def loss(x):
            return matmul(x, w, backend="pallas_interpret").sum()
    else:
        def loss(x):
            q = x[None]
            return flash_attention(q, q, q, causal=True,
                                   backend="pallas_interpret").sum()
    with pytest.raises(NotImplementedError, match="no VJP for the Pallas"):
        jax.grad(loss)(x)
