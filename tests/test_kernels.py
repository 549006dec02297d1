"""Per-kernel validation: sweep shapes/dtypes, assert allclose vs ref oracle.

All Pallas kernels run in interpret mode on CPU (the kernel body executes in
Python); on a real TPU the same code paths compile to Mosaic.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

RNG = np.random.default_rng(42)


# ======================================================================
# maxplus_matmul
# ======================================================================
@pytest.mark.parametrize(
    "m,k,n",
    [(128, 128, 128), (256, 128, 384), (200, 150, 90), (64, 300, 64), (1, 128, 128)],
)
def test_maxplus_matmul_shapes(m, k, n):
    a = RNG.normal(size=(m, k)).astype(np.float32)
    b = RNG.normal(size=(k, n)).astype(np.float32)
    out = ops.maxplus_matmul(a, b)
    exp = ref.maxplus_matmul_ref(jnp.asarray(a), jnp.asarray(b))
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=1e-5)


def test_maxplus_matmul_neginf_identity():
    """-inf is absorbing: the (max,+) identity matrix round-trips."""
    n = 128
    eye = np.full((n, n), -np.inf, dtype=np.float32)
    np.fill_diagonal(eye, 0.0)
    a = RNG.normal(size=(n, n)).astype(np.float32)
    out = ops.maxplus_matmul(a, eye)
    np.testing.assert_allclose(np.asarray(out), a, atol=1e-6)


@pytest.mark.parametrize("g,m,k,n", [(3, 128, 128, 128), (2, 200, 96, 64), (5, 32, 32, 32)])
def test_maxplus_bmm_shapes(g, m, k, n):
    a = RNG.normal(size=(g, m, k)).astype(np.float32)
    b = RNG.normal(size=(g, k, n)).astype(np.float32)
    out = ops.maxplus_bmm(a, b)
    exp = ref.maxplus_bmm_ref(jnp.asarray(a), jnp.asarray(b))
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=1e-5)


def test_maxplus_bmm_kernel_interpret_matches_ref():
    """The batched Pallas kernel itself (interpret mode) against the oracle."""
    from repro.kernels.maxplus_matmul import maxplus_bmm as kern

    a = RNG.normal(size=(2, 128, 128)).astype(np.float32)
    b = RNG.normal(size=(2, 128, 128)).astype(np.float32)
    out = kern(jnp.asarray(a), jnp.asarray(b), interpret=True)
    exp = ref.maxplus_bmm_ref(jnp.asarray(a), jnp.asarray(b))
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=1e-5)


def test_maxplus_bmv_kernel_interpret_matches_ref():
    """The matvec kernel itself (interpret mode): vectors ride as
    (g, 1, k) blocks, the layout the TPU block rule forces."""
    from repro.kernels.maxplus_matmul import maxplus_bmv as kern

    a = RNG.normal(size=(3, 256, 384)).astype(np.float32)
    x = RNG.normal(size=(3, 384)).astype(np.float32)
    out = kern(jnp.asarray(a), jnp.asarray(x), interpret=True)
    exp = ref.maxplus_bmv_ref(jnp.asarray(a), jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=1e-6)


def test_maxplus_bmm_neginf_padding_rows():
    """-inf rows/cols (the EdgeStack padding convention) stay neutral."""
    g, n = 2, 64
    a = RNG.normal(size=(g, n, n)).astype(np.float32)
    b = RNG.normal(size=(g, n, n)).astype(np.float32)
    a[:, n // 2:, :] = -np.inf
    out = np.asarray(ops.maxplus_bmm(a, b))
    assert np.all(np.isneginf(out[:, n // 2:, :]))
    exp = np.asarray(ref.maxplus_bmm_ref(jnp.asarray(a), jnp.asarray(b)))
    np.testing.assert_allclose(out[:, : n // 2], exp[:, : n // 2], atol=1e-5)


def test_maxplus_matmul_associativity():
    a = RNG.normal(size=(64, 64)).astype(np.float32)
    b = RNG.normal(size=(64, 64)).astype(np.float32)
    c = RNG.normal(size=(64, 64)).astype(np.float32)
    left = ops.maxplus_matmul(np.asarray(ops.maxplus_matmul(a, b)), c)
    right = ops.maxplus_matmul(a, np.asarray(ops.maxplus_matmul(b, c)))
    np.testing.assert_allclose(np.asarray(left), np.asarray(right), atol=1e-4)


# ======================================================================
# lif_crossbar
# ======================================================================
@pytest.mark.parametrize("b,n_in,n_out", [(8, 128, 128), (3, 300, 200), (16, 96, 64)])
@pytest.mark.parametrize("dtype", [np.float32])
def test_lif_crossbar_shapes(b, n_in, n_out, dtype):
    s = (RNG.random((b, n_in)) < 0.2).astype(dtype)
    w = RNG.normal(size=(n_in, n_out)).astype(dtype)
    v = RNG.normal(size=(b, n_out)).astype(dtype)
    out_s, out_v = ops.lif_crossbar_step(s, w, v)
    exp_s, exp_v = ref.lif_crossbar_step_ref(
        jnp.asarray(s), jnp.asarray(w), jnp.asarray(v)
    )
    np.testing.assert_allclose(np.asarray(out_s), np.asarray(exp_s))
    np.testing.assert_allclose(np.asarray(out_v), np.asarray(exp_v), atol=1e-4)


def test_lif_crossbar_threshold_semantics():
    """A neuron exactly at threshold fires and resets."""
    s = np.ones((8, 128), np.float32)
    w = np.zeros((128, 128), np.float32)
    w[:, 0] = 1.0 / 128.0  # column 0 accumulates exactly 1.0 == v_th
    v = np.zeros((8, 128), np.float32)
    out_s, out_v = ops.lif_crossbar_step(s, w, v, leak=0.9, v_th=1.0, v_reset=0.0)
    assert np.all(np.asarray(out_s)[:, 0] >= 0.99)
    assert np.allclose(np.asarray(out_v)[:, 0], 0.0)
    assert np.all(np.asarray(out_s)[:, 1:] == 0)


def test_lif_multi_step_trajectory_matches_ref():
    """Iterated kernel == iterated oracle over 10 steps (state carried)."""
    s = (RNG.random((4, 256)) < 0.3).astype(np.float32)
    w = (RNG.normal(size=(256, 256)) * 0.1).astype(np.float32)
    v_k = np.zeros((4, 256), np.float32)
    v_r = jnp.zeros((4, 256), jnp.float32)
    s_k, s_r = s, jnp.asarray(s)
    for _ in range(10):
        s_k, v_k = ops.lif_crossbar_step(np.asarray(s_k), w, np.asarray(v_k))
        s_r, v_r = ref.lif_crossbar_step_ref(s_r, jnp.asarray(w), v_r)
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_r))
    np.testing.assert_allclose(np.asarray(v_k), np.asarray(v_r), atol=1e-3)


# ======================================================================
# flash_attention
# ======================================================================
@pytest.mark.parametrize(
    "b,hq,hkv,s,d",
    [(1, 2, 2, 128, 64), (2, 4, 2, 256, 64), (1, 8, 1, 384, 128), (1, 2, 2, 200, 64)],
)
def test_flash_attention_causal(b, hq, hkv, s, d):
    q = RNG.normal(size=(b, hq, s, d)).astype(np.float32)
    k = RNG.normal(size=(b, hkv, s, d)).astype(np.float32)
    v = RNG.normal(size=(b, hkv, s, d)).astype(np.float32)
    out = ops.flash_attention(q, k, v, causal=True)
    exp = ref.attention_ref(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=2e-3)


@pytest.mark.parametrize("window", [64, 128, 256])
def test_flash_attention_sliding_window(window):
    b, h, s, d = 1, 2, 384, 64
    q = RNG.normal(size=(b, h, s, d)).astype(np.float32)
    k = RNG.normal(size=(b, h, s, d)).astype(np.float32)
    v = RNG.normal(size=(b, h, s, d)).astype(np.float32)
    out = ops.flash_attention(q, k, v, causal=True, window=window)
    exp = ref.attention_ref(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal=True, window=window
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=2e-3)


def test_flash_attention_bf16():
    b, h, s, d = 1, 2, 256, 64
    q = jnp.asarray(RNG.normal(size=(b, h, s, d)), dtype=jnp.bfloat16)
    k = jnp.asarray(RNG.normal(size=(b, h, s, d)), dtype=jnp.bfloat16)
    v = jnp.asarray(RNG.normal(size=(b, h, s, d)), dtype=jnp.bfloat16)
    out = ops.flash_attention(q, k, v, causal=True)
    exp = ref.attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out, dtype=np.float32),
        np.asarray(exp, dtype=np.float32),
        atol=3e-2,
    )


# ======================================================================
# mamba_scan
# ======================================================================
@pytest.mark.parametrize("B,L,D,N,chunk", [(1, 128, 128, 8, 64), (2, 256, 256, 16, 128),
                                           (1, 200, 128, 16, 64)])
def test_mamba_scan_shapes(B, L, D, N, chunk):
    x = RNG.normal(size=(B, L, D)).astype(np.float32)
    dt = (0.01 + 0.1 * RNG.random((B, L, D))).astype(np.float32)
    a = (-np.exp(RNG.normal(size=(D, N)))).astype(np.float32)
    bm = RNG.normal(size=(B, L, N)).astype(np.float32)
    cm = RNG.normal(size=(B, L, N)).astype(np.float32)
    y, h = ops.mamba_scan(x, dt, a, bm, cm, chunk=chunk)
    ye, he = ref.mamba_scan_ref(
        jnp.asarray(x), jnp.asarray(dt), jnp.asarray(a), jnp.asarray(bm), jnp.asarray(cm)
    )
    np.testing.assert_allclose(np.asarray(y), np.asarray(ye), atol=3e-3)
    np.testing.assert_allclose(np.asarray(h), np.asarray(he), atol=3e-3)


def test_mamba_scan_is_causal():
    """Perturbing the future never changes the past."""
    B, L, D, N = 1, 128, 128, 8
    x = RNG.normal(size=(B, L, D)).astype(np.float32)
    dt = (0.05 * np.ones((B, L, D))).astype(np.float32)
    a = (-np.ones((D, N))).astype(np.float32)
    bm = RNG.normal(size=(B, L, N)).astype(np.float32)
    cm = RNG.normal(size=(B, L, N)).astype(np.float32)
    y1, _ = ops.mamba_scan(x, dt, a, bm, cm, chunk=64)
    x2 = x.copy()
    x2[:, 100:] += 10.0
    y2, _ = ops.mamba_scan(x2, dt, a, bm, cm, chunk=64)
    np.testing.assert_allclose(
        np.asarray(y1)[:, :100], np.asarray(y2)[:, :100], atol=1e-5
    )
    assert not np.allclose(np.asarray(y1)[:, 100:], np.asarray(y2)[:, 100:])


# ======================================================================
# kernel <-> core integration: power iteration uses maxplus kernel
# ======================================================================
def test_power_iteration_with_kernel_matches_howard():
    from repro.core.maxplus import maxplus_matrix, mcm_power_iteration, mcr_howard
    from repro.core.sdfg import SDFG, Channel

    rng = np.random.default_rng(7)
    n = 40
    tau = rng.uniform(1, 5, size=n)
    channels = [Channel(i, i, 1, 1.0, kind="self") for i in range(n)]
    for i in range(n):
        channels.append(Channel(i, (i + 1) % n, 1 if i == n - 1 else 0, 1.0))
    for _ in range(2 * n):
        i, j = int(rng.integers(n)), int(rng.integers(n))
        if i != j:
            channels.append(Channel(i, j, 1, 1.0))
    g = SDFG(n_actors=n, exec_time=tau, channels=channels)
    T = maxplus_matrix(g)
    lam = mcm_power_iteration(T, iters=300, use_kernel=True)
    assert np.isclose(lam, mcr_howard(g), rtol=1e-3)
