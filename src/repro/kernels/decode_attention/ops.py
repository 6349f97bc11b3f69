"""Jitted wrappers: decode attention (dense + paged) with jnp fallback."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention.kernel import (decode_attention,
                                                   decode_attention_paged)
from repro.kernels.decode_attention.ref import (decode_attention_ref,
                                                decode_attention_paged_ref)


@functools.partial(jax.jit, static_argnames=("bkv", "use_pallas",
                                             "interpret"))
def decode_attention_op(q, k, v, cache_len, *, bkv=128, use_pallas=True,
                        interpret=None):
    if use_pallas:
        return decode_attention(q, k, v, cache_len, bkv=bkv,
                                interpret=interpret)
    return decode_attention_ref(q, k, v, cache_len)


@functools.partial(jax.jit, static_argnames=("use_pallas", "gather",
                                             "interpret"))
def decode_attention_paged_op(q, k_pages, v_pages, block_table, cache_lens,
                              *, use_pallas=True, gather=False,
                              interpret=None):
    """Block-table decode attention against the page-pool arenas.

    Three lowerings, one contract (q (B,H,Dh); arenas (P,ps,KV,Dh);
    block_table (B,nb); cache_lens (B,) -> (B,H,Dh)):

      * ``use_pallas`` + ``gather``: gather the table's pages into a
        dense (B, nb*ps) cache IN THE WRAPPER, then run the dense
        flash-decoding kernel — correct everywhere the dense kernel is,
        at the cost of materializing the gathered copy;
      * ``use_pallas`` alone: the block-table-consuming kernel — the
        scalar-prefetched table drives the DMA grid directly, no
        gathered copy (preferred where the grid allows);
      * neither: jnp oracle.

    The arenas may be ONE layer's (num_pages, ps, ...) arena or the
    scan-decode FUSED arena (page axis = n_attn_layers * num_pages,
    DESIGN.md §Sharded-scan-decode) — the contract is unchanged because
    block tables carry absolute page ids: the caller offsets the table
    by ``rank * num_pages`` into its slab, and each slab's first page
    (never allocated) serves as that layer's null/pad page.
    """
    assert k_pages.shape == v_pages.shape, \
        f"K/V arena mismatch: {k_pages.shape} vs {v_pages.shape}"
    assert q.shape[-1] == k_pages.shape[-1], \
        f"head_dim mismatch: q {q.shape} vs arena {k_pages.shape}"
    if use_pallas and gather:
        B = q.shape[0]
        KV, Dh = k_pages.shape[2], k_pages.shape[3]
        k = k_pages[block_table].reshape(B, -1, KV, Dh)
        v = v_pages[block_table].reshape(B, -1, KV, Dh)
        return decode_attention(q, k, v, cache_lens,
                                bkv=k_pages.shape[1], interpret=interpret)
    if use_pallas:
        return decode_attention_paged(q, k_pages, v_pages, block_table,
                                      cache_lens, interpret=interpret)
    return decode_attention_paged_ref(q, k_pages, v_pages, block_table,
                                      cache_lens)
