"""GQA decode attention (one new token vs a long KV cache).

Flash-decoding-style TPU kernel: grid = (B*KV, S/bkv) sweeps the cache
sequence in chunks; the online-softmax state for the single query
position is carried in VMEM scratch across the (sequential) chunk grid
steps — the Pallas analogue of split-KV decode, matching the sequence-
sharded decode layout the serving path uses on the mesh.

Two variants share the online-softmax body:

  * ``decode_attention``       — dense (B, S, KV, Dh) caches, per-row
    valid lengths (continuous batching);
  * ``decode_attention_paged`` — the serving engine's PAGED cache: K/V
    live in (num_pages, page_size, KV, Dh) arenas and each row's pages
    arrive via a block table; its grid is (B, n_blocks) and one grid
    step serves every KV head of a page.  The table rides in as a
    scalar-prefetch operand (``pltpu.PrefetchScalarGridSpec``) so the
    BlockSpec index map dereferences it directly — each grid step DMAs
    exactly the page it needs from the arena, no gathered copy of the
    cache is ever materialized (the gather-in-the-wrapper fallback lives
    in ops.py).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

NEG_INF = -1e30


def _online_softmax_step(q, k, v, pos, cache_len, acc_ref, m_ref, l_ref):
    """One KV-chunk update of the carried (acc, m, l) state.
    q (G,Dh) pre-scaled f32; k/v (bkv,Dh) f32; pos (G,bkv) absolute."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (G,bkv)
    s = jnp.where(pos < cache_len, s, NEG_INF)
    m_prev, l_prev = m_ref[...], l_ref[...]              # (G,1)
    m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_prev * alpha + jnp.sum(p, -1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new


def _dec_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
                *, bkv: int, nkv: int, kv_heads: int, scale: float):
    """q_ref (1,G,Dh); k/v_ref (1,bkv,Dh); scratch acc (G,Dh), m/l (G,1)."""
    ci = pl.program_id(1)
    _, G, Dh = q_ref.shape
    cache_len = len_ref[pl.program_id(0) // kv_heads]

    @pl.when(ci == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32) * scale             # (G, Dh)
    k = k_ref[0].astype(jnp.float32)                     # (bkv, Dh)
    v = v_ref[0].astype(jnp.float32)
    pos = ci * bkv + jax.lax.broadcasted_iota(jnp.int32, (G, bkv), 1)
    _online_softmax_step(q, k, v, pos, cache_len, acc_ref, m_ref, l_ref)

    @pl.when(ci == nkv - 1)
    def _store():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-20)
                    ).astype(o_ref.dtype)


def decode_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                     cache_len, *, bkv: int = 128,
                     interpret: Optional[bool] = None) -> jnp.ndarray:
    """q (B,H,Dh); k/v (B,S,KV,Dh); cache_len: #valid positions (scalar
    or (B,) per row).  Returns (B,H,Dh)."""
    B, H, Dh = q.shape
    S, KV = k.shape[1], k.shape[2]
    G = H // KV
    assert S % bkv == 0
    nkv = S // bkv
    scale = 1.0 / math.sqrt(Dh)
    qg = q.reshape(B, KV, G, Dh).reshape(B * KV, G, Dh)
    kk = k.transpose(0, 2, 1, 3).reshape(B * KV, S, Dh)
    vv = v.transpose(0, 2, 1, 3).reshape(B * KV, S, Dh)
    clen = jnp.broadcast_to(
        jnp.asarray(cache_len, jnp.int32).reshape(-1), (B,))
    kern = functools.partial(_dec_kernel, bkv=bkv, nkv=nkv, kv_heads=KV,
                             scale=scale)
    out = pl.pallas_call(
        kern,
        grid=(B * KV, nkv),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, G, Dh), lambda b, c: (b, 0, 0)),
            pl.BlockSpec((1, bkv, Dh), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, bkv, Dh), lambda b, c: (b, c, 0)),
        ],
        out_specs=pl.BlockSpec((1, G, Dh), lambda b, c: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B * KV, G, Dh), q.dtype),
        scratch_shapes=[pltpu.VMEM((G, Dh), jnp.float32),
                        pltpu.VMEM((G, 1), jnp.float32),
                        pltpu.VMEM((G, 1), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(clen, qg, kk, vv)
    return out.reshape(B, KV, G, Dh).reshape(B, H, Dh)


def _paged_kernel(tbl_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                  acc_ref, m_ref, l_ref, *, page_size: int, scale: float):
    """Block-table decode body for one row and one page.  q_ref
    (1,KV,G,Dh); k/v_ref (1,ps,KV,Dh) — the whole page the index map
    selected from the arena via ``tbl_ref``; scratch acc (KV,G,Dh),
    m/l (KV,G,1).  The KV heads are a static loop, so each page is
    DMAed once for all of them."""
    ci = pl.program_id(1)
    nb = pl.num_programs(1)
    _, KV, G, Dh = q_ref.shape
    cache_len = len_ref[pl.program_id(0)]

    @pl.when(ci == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    pos = ci * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (G, page_size), 1)
    for h in range(KV):
        q = q_ref[0, h].astype(jnp.float32) * scale      # (G, Dh)
        k = k_ref[0, :, h, :].astype(jnp.float32)        # (ps, Dh)
        v = v_ref[0, :, h, :].astype(jnp.float32)
        _online_softmax_step(q, k, v, pos, cache_len, acc_ref.at[h],
                             m_ref.at[h], l_ref.at[h])

    @pl.when(ci == nb - 1)
    def _store():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-20)
                    ).astype(o_ref.dtype)


def decode_attention_paged(q: jnp.ndarray, k_pages: jnp.ndarray,
                           v_pages: jnp.ndarray, block_table: jnp.ndarray,
                           cache_lens, *, interpret: Optional[bool] = None
                           ) -> jnp.ndarray:
    """Paged flash-decoding: the kernel consumes the block table.

    q (B,H,Dh); k/v_pages (num_pages, page_size, KV, Dh);
    block_table (B, n_blocks) page ids (position order, padded rows
    point at an all-masked page); cache_lens scalar or (B,).  The grid
    is (B, n_blocks) and the K/V BlockSpec index maps read
    ``block_table`` from SMEM (scalar prefetch) to pick which arena
    page each step DMAs — the gather IS the grid.  A K/V block is one
    whole page, so its last two dimensions are the arena's (KV, Dh),
    the tiling Mosaic accepts for any head count.
    """
    B, H, Dh = q.shape
    ps, KV = k_pages.shape[1], k_pages.shape[2]
    nb = block_table.shape[1]
    G = H // KV
    scale = 1.0 / math.sqrt(Dh)
    qg = q.reshape(B, KV, G, Dh)
    clen = jnp.broadcast_to(
        jnp.asarray(cache_lens, jnp.int32).reshape(-1), (B,))
    tbl = jnp.asarray(block_table, jnp.int32)
    kern = functools.partial(_paged_kernel, page_size=ps, scale=scale)

    def kv_map(b, c, tbl_ref, len_ref):
        return (tbl_ref[b, c], 0, 0, 0)

    def row_map(b, c, tbl_ref, len_ref):
        return (b, 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, nb),
        in_specs=[
            pl.BlockSpec((1, KV, G, Dh), row_map),
            pl.BlockSpec((1, ps, KV, Dh), kv_map),
            pl.BlockSpec((1, ps, KV, Dh), kv_map),
        ],
        out_specs=pl.BlockSpec((1, KV, G, Dh), row_map),
        scratch_shapes=[pltpu.VMEM((KV, G, Dh), jnp.float32),
                        pltpu.VMEM((KV, G, 1), jnp.float32),
                        pltpu.VMEM((KV, G, 1), jnp.float32)],
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, Dh), q.dtype),
        interpret=resolve_interpret(interpret),
    )(tbl, clen, qg, k_pages, v_pages)
    return out.reshape(B, H, Dh)
