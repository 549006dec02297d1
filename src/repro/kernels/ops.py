"""Public jit'd wrappers around the Pallas kernels.

Handle arbitrary shapes (pad to block multiples with the correct neutral
element), select interpret mode automatically on non-TPU backends (the
kernel body then executes in Python on CPU — our validation mode), and fall
back to the pure-jnp reference for shapes where a kernel launch would not
pay off.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import ref
from .flash_attention import flash_attention as _flash
from .lif_crossbar import lif_crossbar_step as _lif
from .mamba_scan import mamba_chunk_scan as _mamba_chunk
from .maxplus_matmul import maxplus_bmm as _maxplus_bmm
from .maxplus_matmul import maxplus_bmv as _maxplus_bmv
from .maxplus_matmul import maxplus_matmul as _maxplus


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _on_accelerator() -> bool:
    """Any non-CPU jax device visible (TPU *or* GPU)?

    Backend auto-selection must not key on ``default_backend() == "tpu"``
    alone: on a CUDA host that test is false and the exact analysis would
    silently fall back to host numpy.  A backend that fails to initialise
    raises here rather than reading as "no accelerator".
    """
    return any(d.platform != "cpu" for d in jax.devices())


def _pad_to(x: jax.Array, mults: tuple[int, ...], fill: float) -> jax.Array:
    pads = []
    for dim, m in zip(x.shape, mults):
        target = -(-dim // m) * m
        pads.append((0, target - dim))
    if all(p == (0, 0) for p in pads):
        return x
    return jnp.pad(x, pads, constant_values=fill)


# ======================================================================
# (max,+) matmul / matvec
# ======================================================================
def maxplus_matmul(a, b, *, interpret: bool | None = None):
    """C = A (x) B for arbitrary shapes (pads with -inf)."""
    a = jnp.asarray(a, dtype=jnp.float32)
    b = jnp.asarray(b, dtype=jnp.float32)
    m, k = a.shape
    _, n = b.shape
    if m * n * k < 64**3:  # launch not worth it; oracle is exact
        return ref.maxplus_matmul_ref(a, b)
    if interpret is None:
        interpret = not _on_tpu()
    bm = bn = bk = 128
    ap = _pad_to(a, (bm, bk), float("-inf"))
    bp = _pad_to(b, (bk, bn), float("-inf"))
    out = _maxplus(ap, bp, bm=bm, bn=bn, bk=bk, interpret=interpret)
    return out[:m, :n]


def maxplus_matvec(a, x, *, interpret: bool | None = None):
    """t' = A (x) t.  Matvec has no MXU/VPU win at SDFG sizes; the power
    iteration batches vectors through :func:`maxplus_matmul` when wide."""
    a = jnp.asarray(a, dtype=jnp.float32)
    x = jnp.asarray(x, dtype=jnp.float32)
    return ref.maxplus_matvec_ref(a, x)


def maxplus_bmv(a, x, *, interpret: bool | None = None):
    """y[g] = A[g] (x) x[g] for arbitrary shapes (pads with -inf).

    One launch advances every candidate's Eq.-4 recursion by one step.  On
    CPU / small stacks the jnp oracle is exact and cheaper than an
    interpret-mode launch.
    """
    a = jnp.asarray(a, dtype=jnp.float32)
    x = jnp.asarray(x, dtype=jnp.float32)
    g, m, k = a.shape
    if interpret is None:
        interpret = not _on_tpu()
    if interpret or g * m * k < 64**3:
        return ref.maxplus_bmv_ref(a, x)
    bm = bk = 128
    ap = _pad_to(a, (1, bm, bk), float("-inf"))
    xp = _pad_to(x, (1, bk), float("-inf"))
    out = _maxplus_bmv(ap, xp, bm=bm, bk=bk, interpret=False)
    return out[:, :m]


def maxplus_bmm(a, b, *, interpret: bool | None = None):
    """C[g] = A[g] (x) B[g] for arbitrary shapes (pads with -inf).

    The batched-analysis workhorse: one candidate graph per batch row.  On
    TPU the stack streams through the batched Pallas kernel; elsewhere the
    jnp oracle is exact and avoids interpret-mode launch overhead.
    """
    a = jnp.asarray(a, dtype=jnp.float32)
    b = jnp.asarray(b, dtype=jnp.float32)
    g, m, k = a.shape
    _, _, n = b.shape
    if interpret is None:
        interpret = not _on_tpu()
    if interpret or m * n * k < 64**3:
        return ref.maxplus_bmm_ref(a, b)
    bm = bn = bk = 128
    ap = _pad_to(a, (1, bm, bk), float("-inf"))
    bp = _pad_to(b, (1, bk, bn), float("-inf"))
    out = _maxplus_bmm(ap, bp, bm=bm, bn=bn, bk=bk, interpret=False)
    return out[:, :m, :n]


# ======================================================================
# fused LIF crossbar step
# ======================================================================
def lif_crossbar_step(
    spikes, weights, v, *, leak=0.9, v_th=1.0, v_reset=0.0,
    interpret: bool | None = None,
):
    spikes = jnp.asarray(spikes)
    weights = jnp.asarray(weights)
    v = jnp.asarray(v)
    b, n_in = spikes.shape
    _, n_out = weights.shape
    if interpret is None:
        interpret = not _on_tpu()
    bb = 8
    sp = _pad_to(spikes, (bb, 128), 0.0)
    wp = _pad_to(weights, (128, 128), 0.0)
    vp = _pad_to(v, (bb, 128), 0.0)
    out_s, out_v = _lif(
        sp, wp, vp, leak=leak, v_th=v_th, v_reset=v_reset,
        bb=bb, bn=128, bk=128, interpret=interpret,
    )
    return out_s[:b, :n_out], out_v[:b, :n_out]


# ======================================================================
# flash attention
# ======================================================================
def flash_attention(
    q, k, v, *, causal=True, window=0, interpret: bool | None = None,
    bq: int = 128, bkv: int = 128,
):
    """(B, Hq, Sq, D) x (B, Hkv, Skv, D) -> (B, Hq, Sq, D).

    Pads Sq/Skv to block multiples; padded kv columns are masked out by the
    causal/window mask plus an explicit validity mask via -inf scores being
    impossible for padded keys (k rows are zero but q_idx >= kv_idx keeps
    padded FUTURE keys out; padding is appended at the end so causal masking
    already excludes it for every real query).
    """
    sq, skv = q.shape[2], k.shape[2]
    if interpret is None:
        interpret = not _on_tpu()
    if not causal and skv % bkv != 0:
        # non-causal padding would attend to padded keys; use the oracle
        return ref.attention_ref(q, k, v, causal=False, window=window)
    qp = _pad_to(q, (1, 1, bq, 1), 0.0)
    kp = _pad_to(k, (1, 1, bkv, 1), 0.0)
    vp = _pad_to(v, (1, 1, bkv, 1), 0.0)
    if kp.shape[2] > qp.shape[2] and causal and skv == sq:
        qp = _pad_to(q, (1, 1, kp.shape[2], 1), 0.0)
    out = _flash(
        qp, kp, vp, causal=causal, window=window,
        bq=min(bq, qp.shape[2]), bkv=min(bkv, kp.shape[2]),
        interpret=interpret,
    )
    return out[:, :, :sq, :]


# ======================================================================
# mamba selective scan (two-phase chunked)
# ======================================================================
def mamba_scan(
    x, dt, a, b, c, *, chunk: int = 128, interpret: bool | None = None,
):
    """Full-sequence S6 scan via the chunked kernel. Returns (y, h_final)."""
    B, L, D = x.shape
    N = a.shape[1]
    if interpret is None:
        interpret = not _on_tpu()
    if L % chunk != 0:
        pad = -(-L // chunk) * chunk - L
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        b = jnp.pad(b, ((0, 0), (0, pad), (0, 0)))
        c = jnp.pad(c, ((0, 0), (0, pad), (0, 0)))
    Lp = x.shape[1]
    n_chunks = Lp // chunk
    bd = min(128, D)

    zeros = jnp.zeros((B, n_chunks, D, N), jnp.float32)
    # phase 1: local scans from zero state -> per-chunk final local states
    _, s_local = _mamba_chunk(
        x, dt, a, b, c, zeros, chunk=chunk, bd=bd, interpret=interpret
    )
    # host combine: H_init(c) = Decay(c-1) * H_init(c-1) + S_local(c-1)
    dt_sum = dt.reshape(B, n_chunks, chunk, D).sum(axis=2)        # (B,C,D)
    decay_chunk = jnp.exp(dt_sum[..., None] * a[None, None])       # (B,C,D,N)

    def comb(h, inp):
        dec, s = inp
        h_next = dec * h + s
        return h_next, h

    (_, h_inits) = jax.lax.scan(
        comb,
        jnp.zeros((B, D, N), jnp.float32),
        (jnp.moveaxis(decay_chunk, 1, 0), jnp.moveaxis(s_local, 1, 0)),
    )
    h_inits = jnp.moveaxis(h_inits, 0, 1)                          # (B,C,D,N)
    # phase 2: true scan from the propagated initial states
    y, h_fin = _mamba_chunk(
        x, dt, a, b, c, h_inits, chunk=chunk, bd=bd, interpret=interpret
    )
    return y[:, :L], h_fin[:, -1]
