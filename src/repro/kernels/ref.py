"""Pure-jnp reference oracles for every Pallas kernel.

These are not toy references: they are the exact math the kernels implement, written
chunked (flash-style online softmax, chunkwise SSM/mLSTM recurrences) so that they
(a) serve as autodiff-able model execution paths on CPU and in the 512-device dry-run,
(b) have the same numerics contract as the kernels (fp32 accumulation, stabilized
exponents), and (c) define memory profiles that actually fit HBM at 32k-524k tokens.

``naive_*`` variants materialize everything and exist only as small-shape test oracles.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.util import probe_block, rscan

NEG_INF = -1e30


# ======================================================================== attention

def naive_attention(q, k, v, *, causal: bool = True, q_offset: int = 0):
    """Small-shape oracle. q: [B,Sq,Hq,D]; k,v: [B,Skv,Hkv,D]; Hq % Hkv == 0."""
    B, Sq, Hq, D = q.shape
    _, Skv, Hkv, _ = k.shape
    G = Hq // Hkv
    qf = q.astype(jnp.float32).reshape(B, Sq, Hkv, G, D)
    s = jnp.einsum("bqhgd,bkhd->bqhgk", qf, k.astype(jnp.float32)) / jnp.sqrt(D)
    if causal:
        qpos = q_offset + jnp.arange(Sq)[:, None]
        kpos = jnp.arange(Skv)[None, :]
        mask = (kpos <= qpos)[None, :, None, None, :]
        s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bqhgk,bkhd->bqhgd", p, v.astype(jnp.float32))
    return o.reshape(B, Sq, Hq, D).astype(q.dtype)


def _pad_to(x, mult, axis):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x, 0
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), pad


def flash_attention(q, k, v, *, causal: bool = True, q_offset=0,
                    block_q: int = 512, block_kv: int = 512,
                    return_lse: bool = False):
    """Chunked online-softmax attention (GQA-aware).

    q: [B, Sq, Hq, D]; k, v: [B, Skv, Hkv, D]. ``q_offset`` is the absolute position
    of q[0] (for prefill continuation / decode batches); may be a traced scalar.
    Returns [B, Sq, Hq, D] (and LSE [B, Sq, Hq] if requested).
    """
    B, Sq, Hq, D = q.shape
    _, Skv, Hkv, _ = k.shape
    assert Hq % Hkv == 0, (Hq, Hkv)
    G = Hq // Hkv
    block_q = probe_block(min(block_q, max(Sq, 16)), Sq)
    block_kv = probe_block(min(block_kv, max(Skv, 16)), Skv)
    scale = 1.0 / jnp.sqrt(jnp.float32(D))

    qp, _ = _pad_to(q.reshape(B, Sq, Hkv, G, D), block_q, axis=1)
    kp, _ = _pad_to(k, block_kv, axis=1)
    vp, _ = _pad_to(v, block_kv, axis=1)
    nq, nk = qp.shape[1] // block_q, kp.shape[1] // block_kv

    kp = kp.reshape(B, nk, block_kv, Hkv, D)
    vp = vp.reshape(B, nk, block_kv, Hkv, D)
    qp = qp.reshape(B, nq, block_q, Hkv, G, D)

    def one_batch(qb_all, k_all, v_all):
        # qb_all: [nq, bq, Hkv, G, D]; k_all, v_all: [nk, bk, Hkv, D]

        def q_block(_, inp):
            qi, qb = inp
            q_pos = q_offset + qi * block_q + jnp.arange(block_q)      # absolute positions

            def kv_block(carry, inputs):
                m, l, acc = carry
                ki, kb, vb = inputs
                kv_pos = ki * block_kv + jnp.arange(block_kv)
                # native-dtype dots with fp32 accumulation + a bf16 P matrix:
                # halves the S^2 HBM traffic of the score chain vs fp32 upcasts
                # (EXPERIMENTS.md §Perf, starcoder2 prefill iteration 2)
                s = jnp.einsum("qhgd,khd->qhgk", qb, kb,
                               preferred_element_type=jnp.float32) * scale
                valid = (kv_pos[None, :] < Skv)
                if causal:
                    valid = valid & (kv_pos[None, :] <= q_pos[:, None])
                maskv = valid[:, None, None, :]
                s = jnp.where(maskv, s, NEG_INF)
                m_new = jnp.maximum(m, jnp.max(s, axis=-1))
                p = jnp.exp(s - m_new[..., None]) * maskv
                corr = jnp.exp(m - m_new)
                l_new = l * corr + jnp.sum(p, axis=-1)
                acc_new = acc * corr[..., None] + jnp.einsum(
                    "qhgk,khd->qhgd", p.astype(vb.dtype), vb,
                    preferred_element_type=jnp.float32)
                return (m_new, l_new, acc_new), None

            m0 = jnp.full((block_q, Hkv, G), NEG_INF, jnp.float32)
            l0 = jnp.zeros((block_q, Hkv, G), jnp.float32)
            a0 = jnp.zeros((block_q, Hkv, G, D), jnp.float32)
            (m, l, acc), _ = rscan(
                kv_block, (m0, l0, a0), (jnp.arange(nk), k_all, v_all))
            l_safe = jnp.where(l == 0, 1.0, l)
            return None, (acc / l_safe[..., None], m + jnp.log(l_safe))

        _, (outs, lses) = rscan(q_block, None, (jnp.arange(nq), qb_all))
        return outs, lses

    outs, lses = jax.vmap(one_batch)(qp, kp, vp)                       # [B,nq,bq,Hkv,G,*]
    out = outs.reshape(B, nq * block_q, Hq, D)[:, :Sq].astype(q.dtype)
    if return_lse:
        lse = lses.reshape(B, nq * block_q, Hq)[:, :Sq]
        return out, lse
    return out


def decode_attention(q, k_cache, v_cache, length, *, block_kv: int = 1024,
                     return_stats: bool = False):
    """Single-token attention against a KV cache (flash-decoding math).

    q: [B, Hq, D]; k_cache, v_cache: [B, Hkv, S, D] (head-major, the cache
    layout the models keep); length: int32 [] or [B] —
    positions >= length are masked out. Returns [B, Hq, D], or the raw online-
    softmax stats (m, l, acc) shaped [B,Hkv,G(,D)] for cross-shard LSE merging
    (distributed flash decoding).
    """
    B, Hq, D = q.shape
    _, Hkv, S, _ = k_cache.shape
    G = Hq // Hkv
    block_kv = probe_block(min(block_kv, max(S, 16)), S)
    scale = 1.0 / jnp.sqrt(jnp.float32(D))
    lengths = jnp.broadcast_to(jnp.asarray(length, jnp.int32), (B,))

    # NOTE perf: the cache is consumed in place via dynamic_slice per block — no
    # pad/reshape/transpose copies — and the dots run on the native dtype with
    # fp32 accumulation (preferred_element_type), exactly like the Pallas kernel.
    # This matters: layout copies + fp32 upcasts were ~7x the fundamental HBM
    # traffic of this op (EXPERIMENTS.md §Perf, qwen2.5 decode iteration 2).
    nk = -(-S // block_kv)
    qr = q.reshape(B, Hkv, G, D)
    if S % block_kv != 0:   # pad only when truly ragged (rare: S is a power of 2)
        pad = (-S) % block_kv
        k_cache = jnp.pad(k_cache, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, 0), (0, pad), (0, 0)))

    def kv_block(carry, ki):
        m, l, acc = carry
        start = ki * block_kv
        kb = jax.lax.dynamic_slice_in_dim(k_cache, start, block_kv, axis=2)
        vb = jax.lax.dynamic_slice_in_dim(v_cache, start, block_kv, axis=2)
        kv_pos = start + jnp.arange(block_kv)
        s = jnp.einsum("bhgd,bhkd->bhgk", qr, kb,
                       preferred_element_type=jnp.float32) * scale
        valid = ((kv_pos[None, :] < jnp.minimum(lengths, S)[:, None])
                 & (kv_pos[None, :] < S))                              # [B,bk]
        s = jnp.where(valid[:, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None]) * valid[:, None, None, :]
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhgk,bhkd->bhgd", p.astype(k_cache.dtype), vb,
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, Hkv, G), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Hkv, G), jnp.float32)
    a0 = jnp.zeros((B, Hkv, G, D), jnp.float32)
    (m, l, acc), _ = rscan(kv_block, (m0, l0, a0), jnp.arange(nk))
    if return_stats:
        return m, l, acc
    l_safe = jnp.where(l == 0, 1.0, l)
    return (acc / l_safe[..., None]).reshape(B, Hq, D).astype(q.dtype)


def paged_decode_attention(q, k_pages, v_pages, page_table, lengths, layer, *,
                           block_kv: int = 1024):
    """Single-token attention against a paged KV cache (oracle by gather).

    q: [B, Hq, D]; k_pages, v_pages: [L, P, Hkv, page_size, D]; page_table:
    [B, max_pages] s32 (page ids per sequence, unused entries point at the
    null page 0); lengths: [] or [B] s32; layer: [] s32. Gathers each
    sequence's page chain out of ``layer``'s pool into a contiguous cache and
    applies the exact contiguous decode math — positions >= length (including
    everything a null-page entry contributes) are masked there.
    """
    B = q.shape[0]
    _, _, Hkv, page_size, D = k_pages.shape
    max_pages = page_table.shape[1]
    table = jnp.asarray(page_table, jnp.int32)
    layer = jnp.asarray(layer, jnp.int32)

    def gather(pages):                  # [B, max_pages, Hkv, ps, D] -> [B, Hkv, S, D]
        return jnp.swapaxes(pages[layer, table], 1, 2).reshape(
            B, Hkv, max_pages * page_size, D)

    k, v = gather(k_pages), gather(v_pages)
    return decode_attention(q, k, v, lengths, block_kv=block_kv)


# ================================================================== selective scan

def selective_scan(x, dt, a_log, b, c, d_skip, h0=None, *, block: int = 16):
    """Mamba selective scan, chunked with in-chunk associative scan.

    x, dt: [B, S, Di]; a_log: [Di, Ds]; b, c: [B, S, Ds]; d_skip: [Di].
    h0: optional [B, Di, Ds]. Returns (y [B, S, Di], h_final [B, Di, Ds]).
    Recurrence: h_t = exp(dt_t * A) h_{t-1} + (dt_t * x_t) B_t ;  y_t = C_t . h_t + D x_t
    """
    B, S, Di = x.shape
    Ds = a_log.shape[1]
    block = probe_block(min(block, S), S, target_iters=2)
    a = -jnp.exp(a_log.astype(jnp.float32))                            # [Di, Ds], < 0

    xp, pad = _pad_to(x, block, 1)
    dtp, _ = _pad_to(dt, block, 1)
    bp, _ = _pad_to(b, block, 1)
    cp, _ = _pad_to(c, block, 1)
    nchunks = xp.shape[1] // block

    def chunk(h, inputs):
        xb, dtb, bb, cb = inputs                                       # [B, blk, ...]
        dtf = dtb.astype(jnp.float32)
        la = dtf[..., None] * a                                        # [B,blk,Di,Ds] (<0)
        decay = jnp.exp(la)
        bx = (dtf * xb.astype(jnp.float32))[..., None] * bb.astype(jnp.float32)[:, :, None, :]

        def combine(e1, e2):
            a1, u1 = e1
            a2, u2 = e2
            return a1 * a2, a2 * u1 + u2

        pref_a, pref_u = jax.lax.associative_scan(combine, (decay, bx), axis=1)
        h_t = pref_a * h[:, None] + pref_u                             # [B,blk,Di,Ds]
        yb = jnp.einsum("btds,bts->btd", h_t, cb.astype(jnp.float32))
        yb = yb + xb.astype(jnp.float32) * d_skip.astype(jnp.float32)
        return h_t[:, -1], yb

    h0 = jnp.zeros((B, Di, Ds), jnp.float32) if h0 is None else h0.astype(jnp.float32)
    xs = tuple(t.reshape(B, nchunks, block, *t.shape[2:]).swapaxes(0, 1)
               for t in (xp, dtp, bp, cp))
    h_final, ys = rscan(chunk, h0, xs)
    y = ys.swapaxes(0, 1).reshape(B, nchunks * block, Di)[:, :S]
    return y.astype(x.dtype), h_final


def mamba_step(x_t, dt_t, a_log, b_t, c_t, d_skip, h):
    """One decode step. x_t, dt_t: [B, Di]; b_t, c_t: [B, Ds]; h: [B, Di, Ds]."""
    a = -jnp.exp(a_log.astype(jnp.float32))
    dtf = dt_t.astype(jnp.float32)
    decay = jnp.exp(dtf[..., None] * a)                                # [B,Di,Ds]
    h_new = decay * h + (dtf * x_t.astype(jnp.float32))[..., None] * b_t.astype(jnp.float32)[:, None, :]
    y = jnp.einsum("bds,bs->bd", h_new, c_t.astype(jnp.float32))
    y = y + x_t.astype(jnp.float32) * d_skip.astype(jnp.float32)
    return y.astype(x_t.dtype), h_new


# ========================================================================== mLSTM

def mlstm_chunked(q, k, v, i_raw, f_raw, state=None, *, block: int = 64):
    """Chunkwise-parallel stabilized mLSTM (xLSTM [arXiv:2405.04517] parallel form).

    q, k: [B, S, H, Dk]; v: [B, S, H, Dv]; i_raw, f_raw: [B, S, H].
    state: optional (C [B,H,Dk,Dv], n [B,H,Dk], m [B,H]).
    Returns (h [B,S,H,Dv], state').
    Gates: log f = logsigmoid(f_raw) (per step), log i = i_raw.
    """
    B, S, H, Dk = q.shape
    Dv = v.shape[-1]
    block = probe_block(min(block, S), S, target_iters=2)
    scale = 1.0 / jnp.sqrt(jnp.float32(Dk))

    qp, pad = _pad_to(q, block, 1)
    kp, _ = _pad_to(k, block, 1)
    vp, _ = _pad_to(v, block, 1)
    # padded steps: forget gate -> keep state (log f = 0 is wrong; use f_raw large -> logsig~0)
    ip, _ = _pad_to(i_raw, block, 1)
    if pad:
        ip = ip.at[:, S:].set(NEG_INF)                                 # no input on pad steps
    fp, _ = _pad_to(f_raw, block, 1)
    if pad:
        fp = fp.at[:, S:].set(60.0)                                    # logsigmoid(60) ~ 0
    nchunks = qp.shape[1] // block

    if state is None:
        C0 = jnp.zeros((B, H, Dk, Dv), jnp.float32)
        n0 = jnp.zeros((B, H, Dk), jnp.float32)
        m0 = jnp.full((B, H), NEG_INF, jnp.float32)
    else:
        C0, n0, m0 = (s.astype(jnp.float32) for s in state)

    causal = jnp.tril(jnp.ones((block, block), bool))

    def chunk(carry, inputs):
        C, n, m = carry
        qb, kb, vb, ib, fb = inputs                                     # [B,blk,H,*]
        logf = jax.nn.log_sigmoid(fb.astype(jnp.float32))               # [B,blk,H]
        F = jnp.cumsum(logf, axis=1)                                    # inclusive prefix
        logi = ib.astype(jnp.float32)
        # per-position stabilizer: m_i = max(F_i + m, F_i + max_{j<=i}(logi_j - F_j))
        g = logi - F                                                    # [B,blk,H]
        gmax = jax.lax.cummax(g, axis=1)
        m_i = F + jnp.maximum(m[:, None], gmax)                         # [B,blk,H]

        qf = qb.astype(jnp.float32) * scale
        # inter-chunk: q_i . C * exp(F_i + m - m_i)
        w_inter = jnp.exp(F + m[:, None] - m_i)                         # [B,blk,H] <= 1
        inter = jnp.einsum("bthk,bhkv->bthv", qf, C) * w_inter[..., None]
        n_inter = n[:, None] * w_inter[..., None]                       # [B,blk,H,Dk]

        # intra-chunk: decay(i,j) = exp(F_i - F_j + logi_j - m_i), j <= i
        dmat = (F[:, :, None] - F[:, None, :] + logi[:, None, :, :] - m_i[:, :, None])
        dmat = jnp.where(causal[None, :, :, None], dmat, NEG_INF)
        w = jnp.exp(dmat)                                               # [B,blk_i,blk_j,H]
        s = jnp.einsum("bihk,bjhk->bijh", qf, kb.astype(jnp.float32))
        sw = s * w
        intra = jnp.einsum("bijh,bjhv->bihv", sw, vb.astype(jnp.float32))
        n_intra = jnp.einsum("bijh,bjhk->bihk", w, kb.astype(jnp.float32))

        num = inter + intra                                             # [B,blk,H,Dv]
        n_i = n_inter + n_intra                                         # [B,blk,H,Dk]
        denom = jnp.abs(jnp.einsum("bthk,bthk->bth", n_i, qf))
        denom = jnp.maximum(denom, jnp.exp(-m_i))
        h = num / denom[..., None]

        # carry update to end of chunk
        F_c = F[:, -1]                                                  # [B,H]
        m_new = F_c + jnp.maximum(m, gmax[:, -1])                       # [B,H]
        w_old = jnp.exp(F_c + m - m_new)                                # [B,H]
        wk = jnp.exp(F_c[:, None] - F + logi - m_new[:, None])          # [B,blk,H]
        C_new = C * w_old[..., None, None] + jnp.einsum(
            "bjhk,bjhv->bhkv", kb.astype(jnp.float32) * wk[..., None], vb.astype(jnp.float32))
        n_new = n * w_old[..., None] + jnp.einsum(
            "bjhk->bhk", kb.astype(jnp.float32) * wk[..., None])
        return (C_new, n_new, m_new), h

    xs = tuple(t.reshape(B, nchunks, block, *t.shape[2:]).swapaxes(0, 1)
               for t in (qp, kp, vp, ip, fp))
    (C, n, m), hs = rscan(chunk, (C0, n0, m0), xs)
    h = hs.swapaxes(0, 1).reshape(B, nchunks * block, H, Dv)[:, :S]
    return h.astype(q.dtype), (C, n, m)


def mlstm_step(q_t, k_t, v_t, i_t, f_t, state):
    """One decode step. q_t,k_t: [B,H,Dk]; v_t: [B,H,Dv]; i_t,f_t: [B,H]."""
    C, n, m = (s.astype(jnp.float32) for s in state)
    Dk = q_t.shape[-1]
    logf = jax.nn.log_sigmoid(f_t.astype(jnp.float32))
    logi = i_t.astype(jnp.float32)
    m_new = jnp.maximum(logf + m, logi)
    wf = jnp.exp(logf + m - m_new)
    wi = jnp.exp(logi - m_new)
    kf = k_t.astype(jnp.float32)
    C_new = wf[..., None, None] * C + wi[..., None, None] * (
        kf[..., :, None] * v_t.astype(jnp.float32)[..., None, :])
    n_new = wf[..., None] * n + wi[..., None] * kf
    qf = q_t.astype(jnp.float32) / jnp.sqrt(jnp.float32(Dk))
    num = jnp.einsum("bhkv,bhk->bhv", C_new, qf)
    denom = jnp.maximum(jnp.abs(jnp.einsum("bhk,bhk->bh", n_new, qf)), jnp.exp(-m_new))
    h = num / denom[..., None]
    return h.astype(q_t.dtype), (C_new, n_new, m_new)


def mlstm_recurrent(q, k, v, i_raw, f_raw, state=None):
    """Sequential oracle for mlstm_chunked (lax.scan over time)."""
    B, S, H, Dk = q.shape
    Dv = v.shape[-1]
    if state is None:
        state = (jnp.zeros((B, H, Dk, Dv), jnp.float32),
                 jnp.zeros((B, H, Dk), jnp.float32),
                 jnp.full((B, H), NEG_INF, jnp.float32))

    def step(carry, inputs):
        q_t, k_t, v_t, i_t, f_t = inputs
        h, new = mlstm_step(q_t, k_t, v_t, i_t, f_t, carry)
        return new, h

    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, i_raw, f_raw))
    state, hs = jax.lax.scan(step, state, xs)
    return jnp.moveaxis(hs, 0, 1), state
