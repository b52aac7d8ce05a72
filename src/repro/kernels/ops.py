"""Dispatch layer between Pallas TPU kernels and the pure-jnp references.

Models call these entry points; the active implementation is selected by
:func:`set_impl` / :func:`impl_scope`:

* ``ref``       — chunked jnp references (CPU tests, 512-device dry-run; the HLO the
                  roofline analysis reads, since Pallas custom-calls hide FLOPs from
                  ``cost_analysis``).
* ``pallas``    — compiled Pallas kernels (TPU execution target).
* ``interpret`` — Pallas kernels in interpret mode (CPU correctness validation).
* ``auto``      — ``pallas`` on TPU backends, ``ref`` elsewhere (default).

On a TPU no entry point falls back to ``ref``: a kernel the chip's compiler
refuses (``selective_scan`` and ``mlstm`` today) raises there.
"""
from __future__ import annotations

import contextlib
import threading

import jax

from repro.kernels import ref

_VALID = ("auto", "ref", "pallas", "interpret")


class _State(threading.local):
    def __init__(self):
        self.impl = "auto"


_STATE = _State()


def set_impl(impl: str) -> None:
    if impl not in _VALID:
        raise ValueError(f"impl must be one of {_VALID}, got {impl!r}")
    _STATE.impl = impl


def get_impl() -> str:
    return _STATE.impl


@contextlib.contextmanager
def impl_scope(impl: str):
    prev = _STATE.impl
    set_impl(impl)
    try:
        yield
    finally:
        _STATE.impl = prev


def _resolved() -> str:
    impl = _STATE.impl
    if impl == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "ref"
    return impl


# ------------------------------------------------------------------ entry points

def attention(q, k, v, *, causal: bool = True, q_offset=0):
    """GQA attention. q: [B,Sq,Hq,D]; k,v: [B,Skv,Hkv,D] -> [B,Sq,Hq,D]."""
    impl = _resolved()
    if impl == "ref":
        return ref.flash_attention(q, k, v, causal=causal, q_offset=q_offset)
    from repro.kernels import flash_attention as fa
    return fa.flash_attention(q, k, v, causal=causal, q_offset=q_offset,
                              interpret=(impl == "interpret"))


def decode_attention(q, k_cache, v_cache, length):
    """Single-token attention vs cache. q: [B,Hq,D]; caches [B,Hkv,S,D]."""
    impl = _resolved()
    if impl == "ref":
        return ref.decode_attention(q, k_cache, v_cache, length)
    from repro.kernels import decode_attention as da
    return da.decode_attention(q, k_cache, v_cache, length,
                               interpret=(impl == "interpret"))


def paged_decode_attention(q, k_pages, v_pages, page_table, lengths, layer):
    """Single-token attention vs a paged KV cache. q: [B,Hq,D]; pages
    [L,P,Hkv,page_size,D]; page_table [B,max_pages] s32; lengths [] or [B];
    layer [] s32 (the pool read)."""
    impl = _resolved()
    if impl == "ref":
        return ref.paged_decode_attention(q, k_pages, v_pages, page_table,
                                          lengths, layer)
    from repro.kernels import paged_decode_attention as pda
    return pda.paged_decode_attention(q, k_pages, v_pages, page_table, lengths,
                                      layer, interpret=(impl == "interpret"))


def selective_scan(x, dt, a_log, b, c, d_skip, h0=None):
    """Mamba selective scan -> (y, h_final)."""
    impl = _resolved()
    if impl == "ref":
        return ref.selective_scan(x, dt, a_log, b, c, d_skip, h0=h0)
    from repro.kernels import selective_scan as ss
    return ss.selective_scan(x, dt, a_log, b, c, d_skip, h0=h0,
                             interpret=(impl == "interpret"))


def mlstm(q, k, v, i_raw, f_raw, state=None):
    """Chunkwise mLSTM -> (h, (C, n, m))."""
    impl = _resolved()
    if impl == "ref":
        return ref.mlstm_chunked(q, k, v, i_raw, f_raw, state=state)
    from repro.kernels import mlstm as mk
    return mk.mlstm(q, k, v, i_raw, f_raw, state=state,
                    interpret=(impl == "interpret"))
