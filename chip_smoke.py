"""Bring-up check: the main path once, on one TPU chip, at qwen2-1.5b's
published widths (28 layers, d_model 1536, 12/2 heads, vocab 151936)
with random weights drawn from ``--seed``.

    python chip_smoke.py [--size full|smoke] [--seed N]

Phases, in order; any failure raises and exits nonzero:

  a. device    — JAX must compute on a TPU; there is no CPU fallback.
  b. serve     — ``launch.serve.serve_batch``: requests that share a
                 256-token prefix, 32 new tokens each; the prefix cache
                 must hit for every request and every token must decode.
  c. specgen   — ``run_specgen(llm="engine", RealEvalBackend())``: the
                 SpecGen loop on the engine with speculative forks, the
                 eval plane compiling matmul candidates with Mosaic; at
                 least one candidate must validate.
  d. numerics  — the served decode step (paged KV, gather lowering)
                 against ``transformer.forward`` on the same tokens.
  e. paged     — the paged Pallas decode kernel, compiled, against the
                 gather oracle at serving widths.

Each phase prints its wall seconds (work that ends on the host or in
``block_until_ready``) and the compile seconds and count inside it.
Compiles land in the persistent cache (``launch.compile_cache``), so a
second run in the same checkout compiles less.  The last line of
standard output is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax                                          # noqa: E402
import jax.numpy as jnp                             # noqa: E402
import numpy as np                                  # noqa: E402

from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

ARCH = "qwen2-1.5b"

# (d) bf16 keeps 8 significant bits (one ulp is 2**-8 = 3.9e-3 of a
# value).  The decode step and the forward pass are different XLA
# programs (one token against the paged cache vs the whole sequence),
# so their f32 accumulations round to bf16 at different points; a
# one-ulp flip per layer, compounding as a random walk over 28 layers,
# is ~sqrt(28) * 3.9e-3 = 2e-2 of the logit scale.  The bound is 2.5x
# that: a wrong position, mask or cache write moves logits by O(1).
LOGIT_TOL = 5e-2
# (e) the kernel and the oracle both accumulate in f32 and round the
# output to bf16 once; the kernel's online softmax sums pages in a
# different order, so an output may land one rounding step away: at
# most ~2 ulp of the output scale, bound 1e-2 (~2.5 ulp).
PAGED_TOL = 1e-2


class CompileMeter:
    """Counts XLA compiles (and persistent-cache loads) via JAX's
    monitoring events: the backend-compile event spans compiling or
    loading one executable."""

    def __init__(self):
        self.count, self.seconds, self.cache_hits = 0, 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += secs

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    @contextlib.contextmanager
    def phase(self, name: str):
        c0, s0, h0 = self.count, self.seconds, self.cache_hits
        t0 = time.perf_counter()
        yield
        print(f"[{name}] wall_s={time.perf_counter() - t0:.3f} "
              f"compile_s={self.seconds - s0:.3f} "
              f"compiles={self.count - c0} "
              f"cache_loads={self.cache_hits - h0}", flush=True)


def require_tpu() -> jax.Device:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, found platform "
                         f"{dev.platform!r} ({dev.device_kind})")
    return dev


def describe_model(size: str, seed: int) -> None:
    from repro.models.registry import get_sized
    cfg = get_sized(ARCH, size)
    print(f"[model] {cfg.name} layers={cfg.num_layers} "
          f"d_model={cfg.d_model} heads={cfg.num_heads}/"
          f"{cfg.num_kv_heads} vocab={cfg.vocab_size} "
          f"params={cfg.param_count()} seed={seed}", flush=True)


def phase_serve(size: str, seed: int) -> None:
    from repro.launch.serve import serve_batch
    n, max_new = 4, 32
    outs, stats = serve_batch(ARCH, size=size, num_requests=n,
                              prompt_len=288, shared_prefix=256,
                              max_new=max_new, seed=seed)
    decoded = sum(len(o) for o in outs)
    print(f"[serve] decoded_tokens={decoded} prefix_hits={stats.hits} "
          f"tokens_reused={stats.tokens_reused}", flush=True)
    assert decoded == n * max_new, f"decoded {decoded} != {n * max_new}"
    assert stats.hits == n, f"prefix-cache hits {stats.hits} != {n}"


def phase_specgen(size: str, seed: int) -> None:
    from repro.search.driver import run_specgen
    from repro.search.real_eval import RealEvalBackend
    ev = RealEvalBackend()
    # T6 (upper-triangular 4096^3 matmul) at seed 0 draws tiles the v5e
    # compiler accepts within three iterations; the search space is
    # mostly illegal on v5e, so another seed may draw none and fail here
    res, _sched, ctl = run_specgen(
        "T6", iterations=3, devices=4, seed=seed, evaluator=ev,
        transport="async", llm="engine",
        engine_opts=dict(size=size, prompt_len=129, reasoning_tokens=256,
                         spec_tokens=32))
    gen, eng = ctl.gen, ctl.gen.engine
    built = ev.builds_started
    refused = ev.builds_refused / built if built else 0.0
    print(f"[specgen] forks={gen.forks} tokens_decoded="
          f"{eng.tokens_decoded} tokens_not_decoded="
          f"{eng.tokens_not_decoded} decode_dispatches="
          f"{eng.decode_dispatches}", flush=True)
    print(f"[specgen] candidates built={built} validated="
          f"{ev.builds_passed} refused_by_compiler={ev.builds_refused} "
          f"refused_share={refused:.3f}", flush=True)
    print(f"[specgen] best_speedup={res.best_speedup:.3f} (priced by "
          f"the cost model, not measured)", flush=True)
    assert gen.forks > 0, "no Engine.fork() happened"
    assert eng.tokens_decoded > 0, "the engine decoded nothing"
    assert ev.builds_passed >= 1, "no candidate validated on the chip"


def phase_numerics(size: str, seed: int, batch: int = 2,
                   length: int = 128) -> None:
    from repro.models import schema
    from repro.models import transformer as T
    from repro.models.layers import Runtime
    from repro.models.registry import get_sized
    from repro.serving.pagepool import PagePool

    cfg = get_sized(ARCH, size)
    params = schema.init_params(cfg, jax.random.PRNGKey(seed))
    toks = jnp.asarray(np.random.RandomState(seed).randint(
        0, cfg.vocab_size, (batch, length)), jnp.int32)
    # the engine's admission: dense rows gathered from fresh pages,
    # prefilled, scattered back into the arena
    pool = PagePool(cfg, max_batch=batch, max_len=length)
    cache = pool.init_cache()
    table = np.asarray([pool.alloc(pool.pages_per_row)
                        for _ in range(batch)])
    rows = pool.gather_rows(cache, table, np.zeros(batch, np.int64))
    _, rows = jax.jit(lambda p, t, c: T.prefill(
        cfg, p, t, cache=c, start_pos=jnp.int32(0),
        valid_len=jnp.int32(length - 1)))(params, toks[:, :-1], rows)
    cache = pool.write_rows(cache, rows, table, 0)
    # the served decode step: paged KV through the block table
    dec, _ = jax.jit(lambda p, t, c, pos, bt: T.decode_step(
        cfg, p, t, c, pos, Runtime(), block_tables=bt))(
            params, toks[:, -1:], cache,
            jnp.full((batch,), length - 1, jnp.int32), jnp.asarray(table))
    full, _ = jax.jit(lambda p, t: T.forward(cfg, p, t))(params, toks)
    want = full[:, -1].astype(jnp.float32)
    scale = float(jnp.max(jnp.abs(want)))
    err = float(jnp.max(jnp.abs(dec.astype(jnp.float32) - want))) / scale
    print(f"[numerics] decode_vs_forward max_rel_err={err:.3e} "
          f"logit_scale={scale:.4f} tol={LOGIT_TOL}", flush=True)
    assert np.isfinite(err) and err <= LOGIT_TOL, err


def phase_paged(size: str, seed: int) -> None:
    from repro.kernels.decode_attention.ops import decode_attention_paged_op
    from repro.models.registry import get_sized

    cfg = get_sized(ARCH, size)
    B, ps, P, max_len = 16, 16, 4096, 2048
    H, KV, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    nb = max_len // ps
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(k1, (B, H, Dh), jnp.bfloat16)
    kp = jax.random.normal(k2, (P, ps, KV, Dh), jnp.bfloat16)
    vp = jax.random.normal(k3, (P, ps, KV, Dh), jnp.bfloat16)
    rs = np.random.RandomState(seed)
    table = jnp.asarray(1 + rs.permutation(P - 1)[: B * nb].reshape(B, nb),
                        jnp.int32)
    lens = jnp.asarray(rs.randint(1, max_len + 1, B), jnp.int32)
    got = decode_attention_paged_op(q, kp, vp, table, lens,
                                    use_pallas=True).block_until_ready()
    want = decode_attention_paged_op(q, kp, vp, table, lens,
                                     use_pallas=False).block_until_ready()
    want = want.astype(jnp.float32)
    scale = float(jnp.max(jnp.abs(want)))
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want))) / scale
    print(f"[paged] kernel_vs_gather max_rel_err={err:.3e} "
          f"out_scale={scale:.4f} tol={PAGED_TOL}", flush=True)
    assert np.isfinite(err) and err <= PAGED_TOL, err


def main(argv=None) -> None:
    from repro.kernels import resolve_interpret
    from repro.models.registry import SIZES

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--size", default="full", choices=SIZES)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = require_tpu()
    cache_dir = enable_compile_cache()
    meter = CompileMeter()
    print(f"[device] platform={dev.platform} kind={dev.device_kind} "
          f"count={jax.device_count()} compile_cache={cache_dir} "
          f"kernels={'interpret' if resolve_interpret() else 'compiled'}",
          flush=True)
    describe_model(args.size, args.seed)
    for name, fn in (("serve", phase_serve), ("specgen", phase_specgen),
                     ("numerics", phase_numerics), ("paged", phase_paged)):
        with meter.phase(name):
            fn(args.size, args.seed)
    stats = dev.memory_stats() or {}
    print(f"[memory] peak_bytes_in_use={stats.get('peak_bytes_in_use')}",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}))


if __name__ == "__main__":
    main()
