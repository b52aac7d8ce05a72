"""Pallas TPU chunkwise-parallel mLSTM (xLSTM matrix-memory cell).

Grid: (B, H, seq_chunks) with chunks innermost/sequential. The matrix memory
C [Dk, Dv], normalizer n [Dk] and stabilizer m live in VMEM scratch and carry
across chunks — the xLSTM state never touches HBM between chunks. Per chunk the
kernel runs the stabilized parallel form (same math as ref.mlstm_chunked):

  intra: D_ij = exp(F_i - F_j + logi_j - m_i) masked causally; (q k^T * D) v
  inter: (q C) * exp(F_i + m_prev - m_i)
  carry: C' = C * exp(F_c + m_prev - m') + sum_j exp(F_c - F_j + logi_j - m') k_j v_j^T

The [chunk, Dk] x [Dk, chunk] score and [chunk, chunk] x [chunk, Dv] value matmuls
are the MXU work; gate/stabilizer algebra rides the VPU.

Oracle: repro.kernels.ref.mlstm_chunked (itself verified against the sequential
recurrence ref.mlstm_recurrent).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
DEFAULT_CHUNK = 64


def _mlstm_kernel(q_ref, k_ref, v_ref, i_ref, f_ref, h_ref,
                  cout_ref, nout_ref, mout_ref,
                  c_scr, n_scr, m_scr, *, chunk: int, n_chunks: int, scale: float):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        c_scr[...] = jnp.zeros_like(c_scr)
        n_scr[...] = jnp.zeros_like(n_scr)
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)

    q = q_ref[0, :, 0, :].astype(jnp.float32) * scale       # [c, Dk]
    k = k_ref[0, :, 0, :].astype(jnp.float32)               # [c, Dk]
    v = v_ref[0, :, 0, :].astype(jnp.float32)               # [c, Dv]
    logi = i_ref[0, :, 0].astype(jnp.float32)               # [c]
    logf = jax.nn.log_sigmoid(f_ref[0, :, 0].astype(jnp.float32))

    F = jnp.cumsum(logf)                                    # [c] inclusive
    g = logi - F
    gmax = jax.lax.cummax(g, axis=0)
    m_prev = m_scr[0, 0]
    m_i = F + jnp.maximum(m_prev, gmax)                     # [c]

    C, n = c_scr[...], n_scr[...]                           # [Dk, Dv], [1, Dk]
    w_inter = jnp.exp(F + m_prev - m_i)                     # [c] <= 1
    inter = jax.lax.dot_general(q, C, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    inter = inter * w_inter[:, None]                        # [c, Dv]
    n_inter = n * w_inter[:, None]                          # [c, Dk]

    idx_i = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    idx_j = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    dmat = F[:, None] - F[None, :] + logi[None, :] - m_i[:, None]
    dmat = jnp.where(idx_j <= idx_i, dmat, NEG_INF)
    w = jnp.exp(dmat)                                       # [c, c]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    sw = s * w
    intra = jax.lax.dot_general(sw, v, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    n_intra = jax.lax.dot_general(w, k, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)

    num = inter + intra                                     # [c, Dv]
    n_i = n_inter + n_intra                                 # [c, Dk]
    denom = jnp.abs(jnp.sum(n_i * q, axis=-1))
    denom = jnp.maximum(denom, jnp.exp(-m_i))
    h_ref[0, :, 0, :] = (num / denom[:, None]).astype(h_ref.dtype)

    # ---- carry update
    F_c = F[-1]
    m_new = F_c + jnp.maximum(m_prev, gmax[-1])
    w_old = jnp.exp(F_c + m_prev - m_new)
    wk = jnp.exp(F_c - F + logi - m_new)                    # [c]
    kw = k * wk[:, None]
    c_scr[...] = C * w_old + jax.lax.dot_general(
        kw, v, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    n_scr[...] = n * w_old + jnp.sum(kw, axis=0)[None, :]
    m_scr[...] = jnp.full_like(m_scr, m_new)

    @pl.when(ic == n_chunks - 1)
    def _final():
        cout_ref[0, 0, :, :] = c_scr[...]
        nout_ref[0, 0, :] = n_scr[0, :]
        mout_ref[0, 0] = m_scr[0, 0]


def mlstm(q, k, v, i_raw, f_raw, state=None, *, chunk: int = DEFAULT_CHUNK,
          interpret: bool = False):
    """q, k: [B,S,H,Dk]; v: [B,S,H,Dv]; gates: [B,S,H] -> (h [B,S,H,Dv], (C,n,m)).

    Fresh-state form only: the kernel targets the long prefill/train sweep, and
    a carried state (decode continuation) is refused rather than run elsewhere.
    """
    if state is not None:
        raise NotImplementedError("the mlstm kernel starts from a fresh state; "
                                  "continue a carried state with impl='ref'")
    B, S, H, Dk = q.shape
    Dv = v.shape[-1]
    chunk = min(chunk, max(8, 1 << (S - 1).bit_length()))
    pad = (-S) % chunk
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        i_raw = jnp.pad(i_raw, ((0, 0), (0, pad), (0, 0)),
                        constant_values=NEG_INF)            # no input on pad steps
        f_raw = jnp.pad(f_raw, ((0, 0), (0, pad), (0, 0)),
                        constant_values=60.0)               # logsigmoid(60) ~ 0
    Sp = q.shape[1]
    nc = Sp // chunk
    scale = 1.0 / float(Dk) ** 0.5

    kernel = functools.partial(_mlstm_kernel, chunk=chunk, n_chunks=nc, scale=scale)
    h, C, n, m = pl.pallas_call(
        kernel,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, 1, Dk), lambda b, hh, ic: (b, ic, hh, 0)),
            pl.BlockSpec((1, chunk, 1, Dk), lambda b, hh, ic: (b, ic, hh, 0)),
            pl.BlockSpec((1, chunk, 1, Dv), lambda b, hh, ic: (b, ic, hh, 0)),
            pl.BlockSpec((1, chunk, 1), lambda b, hh, ic: (b, ic, hh)),
            pl.BlockSpec((1, chunk, 1), lambda b, hh, ic: (b, ic, hh)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, 1, Dv), lambda b, hh, ic: (b, ic, hh, 0)),
            pl.BlockSpec((1, 1, Dk, Dv), lambda b, hh, ic: (b, hh, 0, 0)),
            pl.BlockSpec((1, 1, Dk), lambda b, hh, ic: (b, hh, 0)),
            pl.BlockSpec((1, 1), lambda b, hh, ic: (b, hh)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Sp, H, Dv), q.dtype),
            jax.ShapeDtypeStruct((B, H, Dk, Dv), jnp.float32),
            jax.ShapeDtypeStruct((B, H, Dk), jnp.float32),
            jax.ShapeDtypeStruct((B, H), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((Dk, Dv), jnp.float32),
            pltpu.VMEM((1, Dk), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, i_raw, f_raw)
    return h[:, :S], (C, n, m)
