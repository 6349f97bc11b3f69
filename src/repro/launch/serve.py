"""Serving launcher: batched requests against the generation engine."""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.launch.compile_cache import enable_compile_cache
from repro.models.layers import Runtime
from repro.models.registry import ARCH_IDS, SIZES, get_sized
from repro.models import schema
from repro.serving.engine import Engine
from repro.serving.kvcache import PrefixCacheStore


def serve_batch(arch: str = "qwen2-1.5b", *, size: str = "smoke",
                num_requests: int = 8, prompt_len: int = 32,
                max_new: int = 16, shared_prefix: int = 16, seed: int = 0,
                verbose=True):
    """Serve a batch of requests that share a prompt prefix — the
    prefix cache turns the shared part into a single prefill.
    ``size`` picks the model's widths (``registry.SIZES``); weights are
    random, drawn from ``seed``."""
    cfg = get_sized(arch, size)
    params = schema.init_params(cfg, jax.random.PRNGKey(seed))
    store = PrefixCacheStore(local_budget_bytes=1 << 28,
                             remote_budget_bytes=1 << 28)
    eng = Engine(cfg, params, Runtime(), max_len=prompt_len + max_new + 8,
                 cache_store=store, max_batch=num_requests)
    rs = np.random.RandomState(seed)
    prefix = list(rs.randint(0, cfg.vocab_size, shared_prefix))
    # seed the store with the shared prefix so every request's
    # admission is a partial hit that suffix-prefills only its tail
    warm = eng.submit(prefix + [0], max_new_tokens=1, temperature=0.0)
    eng.run(warm)
    t0 = time.time()
    gids = []
    for i in range(num_requests):
        tail = list(rs.randint(0, cfg.vocab_size, prompt_len - shared_prefix))
        gids.append(eng.submit(prefix + tail, max_new_tokens=max_new,
                               temperature=0.8, seed=seed + i))
    outs_by_gid = eng.run_all()             # continuous-batched decode
    outs = [outs_by_gid[g] for g in gids]
    dt = time.time() - t0
    if verbose:
        print(f"[serve] {num_requests} requests x {max_new} tokens "
              f"in {dt:.2f}s ({num_requests*max_new/dt:.1f} tok/s, "
              f"{eng.decode_dispatches} batched dispatches)")
        print(f"[serve] admission: {eng.suffix_prefill_rows} rows in "
              f"{eng.suffix_prefill_dispatches} bucketed prefill "
              f"dispatches ({eng.admission_dispatches_saved} saved); "
              f"paged KV: {eng.pool.pages_in_use} pages in use "
              f"({eng.cache_bytes()} B), {eng.pool.page_copies} CoW "
              f"copies")
        print(f"[serve] prefix cache: hits={store.stats.hits} "
              f"misses={store.stats.misses} "
              f"tokens_reused={store.stats.tokens_reused} "
              f"recomputed={store.stats.tokens_recomputed}")
    return outs, store.stats


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b", choices=ARCH_IDS)
    ap.add_argument("--size", default="smoke", choices=SIZES,
                    help="smoke toy widths, or the published config")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    args = ap.parse_args()
    enable_compile_cache()
    serve_batch(args.arch, size=args.size, num_requests=args.requests,
                prompt_len=args.prompt_len, max_new=args.max_new,
                seed=args.seed)


if __name__ == "__main__":
    main()
