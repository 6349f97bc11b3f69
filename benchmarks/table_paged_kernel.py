"""Paged decode-attention kernel at production page counts (ROADMAP).

PR 3 wired ``decode_attention_paged`` (the block-table-consuming Pallas
kernel: scalar-prefetched table drives the DMA grid) into the serving
path behind ``Runtime.use_pallas``, with interpret-mode parity pinned
in tests/test_paged.py.  This table is the owed PRODUCTION benchmark:
the direct block-table kernel vs the gather-then-attend lowering
(materialize the gathered cache in the wrapper, run the dense kernel)
at serving-scale page counts, swept over ``page_size`` — which is the
paged kernel's ``bkv``: each grid step consumes exactly one page, so
the page size IS the KV-chunk batch size of the dense kernel's sweep.

Each row reports mean dispatch microseconds for both lowerings and the
derived ``gather/direct`` speed ratio (>1: the direct kernel wins by
skipping the gathered copy).  The kernels compile on a TPU and run in
interpret mode on the CPU (``repro.kernels.resolve_interpret``); each
row is tagged with the mode, and only compiled rows time the device.

Run standalone (``python -m benchmarks.table_paged_kernel``), via
``make bench-smoke`` (reduced sizes), or from benchmarks/run.py.
"""
from __future__ import annotations

import sys
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.kernels import resolve_interpret
from repro.kernels.decode_attention.ops import decode_attention_paged_op


def _inputs(B, H, KV, Dh, S, page_size, num_pages, seed=0):
    rs = np.random.RandomState(seed)
    nb = S // page_size
    assert num_pages > B * nb, "need distinct pages per row + null page"
    q = jnp.asarray(rs.randn(B, H, Dh), jnp.float32)
    k = jnp.asarray(rs.randn(num_pages, page_size, KV, Dh), jnp.float32)
    v = jnp.asarray(rs.randn(num_pages, page_size, KV, Dh), jnp.float32)
    # production-shaped tables: rows at staggered depths over a big,
    # non-contiguous arena (stride so pages are scattered, like a pool
    # after churn)
    tbl = np.zeros((B, nb), np.int32)
    for b in range(B):
        tbl[b] = 1 + (b + np.arange(nb) * B) % (num_pages - 1)
    lens = np.asarray([S - 1 - (b * 7) % (S // 4) for b in range(B)],
                      np.int32)
    return q, k, v, jnp.asarray(tbl), jnp.asarray(lens)


def _time(fn, *args, iters=3, **kw):
    fn(*args, **kw).block_until_ready()          # compile/warm
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args, **kw)
    out.block_until_ready()
    return (time.perf_counter() - t0) / iters * 1e6


def rows(B=8, H=8, KV=2, Dh=64, S=512, num_pages=4096,
         page_sizes=(16, 32, 64), iters=3):
    mode = "interpret" if resolve_interpret() else "compiled"
    out = []
    for ps in page_sizes:
        args = _inputs(B, H, KV, Dh, S, ps, num_pages)
        us_direct = _time(decode_attention_paged_op, *args, iters=iters)
        us_gather = _time(decode_attention_paged_op, *args, gather=True,
                          iters=iters)
        # parity while we're here: both lowerings agree
        a = decode_attention_paged_op(*args)
        b = decode_attention_paged_op(*args, gather=True)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-4)
        tag = f"ps{ps}_{mode}"
        out.append((f"table_paged_kernel_direct_us_{tag}", us_direct,
                    round(us_direct, 1)))
        out.append((f"table_paged_kernel_gather_us_{tag}", us_gather,
                    round(us_gather, 1)))
        out.append((f"table_paged_kernel_gather_over_direct_{tag}",
                    us_direct + us_gather,
                    round(us_gather / max(us_direct, 1e-9), 3)))
    return out


def main() -> None:
    smoke = "--smoke" in sys.argv
    print("name,us_per_call,derived")
    kw = (dict(B=2, H=4, KV=2, Dh=16, S=64, num_pages=64,
               page_sizes=(16, 32), iters=1)
          if smoke else {})
    for name, us, derived in rows(**kw):
        print(f"{name},{us:.0f},{derived}", flush=True)


if __name__ == "__main__":
    main()
