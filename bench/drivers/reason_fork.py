"""Reasoning-with-forks traffic on the serving engine (kind ``reason_fork``).

W kernel-search workflows share one Engine, a closed loop: each runs one
iteration at a time.  An iteration submits a prompt (task, previous
kernel, profiling feedback) and decodes a long reasoning row.  Every few
reasoning tokens a trigger point forks speculative generations off the
reasoning row's current prefix (``Engine.fork``: block-table copy, pages
shared, copy-on-write when a writer reaches a shared page).  A fork that
finishes meets the termination criterion with a fixed probability; then
the reasoning row and its live sibling forks are cancelled and the next
iteration starts.  A reasoning row that reaches its length ends the
iteration too.

The traffic uses only the Engine's public ``submit``/``fork``/
``subscribe``/``cancel``/``kick`` (set-up's warm-up also drives the page
pool's scatters on ``Engine._cache``: the program has no warm-up entry
for them); the engine runs on its event-loop pump
with an async ``TransportPlane``, and the window is ``EventLoop.run``
stopped by the wall clock.  Every size and coin is drawn in stratified
blocks of the traffic's distributions (``lib.traffic``); see
``SCHEDULE`` below for what the seed draws.  A small share of rows
decodes greedily, for the check; no greedy row has a greedy parent or
sibling, whose tokens would repeat its own.
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from bench.lib import traffic as T
from bench.lib import weights

# The schedule (every size, trigger point, termination coin and which
# rows decode greedily) is drawn in one order, the same for every seed:
# a run's work then depends on the traffic file alone, and two seeds
# differ only in the data (prompt tokens, sampled tokens, the rows the
# check draws).  With the order following the seed, decode_tok_s spread
# 18-28 % between seeds against 0-5 % between two runs of one seed, on
# an earlier form of this traffic; so a change to the prefix store is
# measured on this one order.
SCHEDULE = 0

# The window opens after the loop has run, on the host, as many decode
# steps as this many median reasoning rows take: each workflow has been
# through several iterations by then, so nothing of the common start
# at step 0 is left in its phase.
STEADY_ITERATIONS = 16


def _pow2(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length()


class Workflow:
    def __init__(self, wid: int, streams: Dict[str, T.Stream]):
        self.wid = wid
        self.streams = streams
        self.iteration = -1
        self.prompt = 0                   # this iteration's prompt length
        self.length = 0                   # its reasoning length
        self.greedy = False               # its reasoning row is greedy
        self.reason: Optional[int] = None
        self.forks: set = set()           # live fork ids
        self.sim_forks: List[list] = []   # [greedy, tokens left]
        self.next_trigger = 0
        self.reason_tokens = 0

    def take(self, stream: str):
        return self.streams[stream].take()


class Driver:
    """One cell's engine, warm-up, window and correctness sample."""

    def __init__(self, h):
        self.h = h                        # the harness context
        self.cfg = h.config               # the configuration file
        self.tr = h.traffic               # the traffic file
        self.seed = h.seed
        self.rows: Dict[int, dict] = {}   # gen_id -> row record
        self.times: Dict[int, List[float]] = {}
        self.step_ctx: Dict[int, List[int]] = {}
        self.record_steps = False
        self.attempted = 0
        self.iterations_started = 0
        self.forks_started = 0
        self.forks_finished = 0
        self.in_window = False

    # ------------------------------------------------------------ set-up
    def setup(self) -> None:
        from repro.core.clock import EventLoop
        from repro.models.layers import Runtime
        from repro.serving.engine import Engine
        from repro.serving.transport import TransportConfig, TransportPlane

        h, tr = self.h, self.tr
        self.params = weights.make_params(self.cfg, self.seed)
        h.check_params(self.params)
        self.loop = h.make_loop(EventLoop)
        self.plane = TransportPlane(loop=self.loop,
                                    cfg=TransportConfig(mode="async"))
        self.eng = Engine(h.program_cfg, self.params, Runtime(),
                          max_len=tr["max_len"], max_batch=tr["max_batch"],
                          transport=self.plane, clocking="event",
                          num_pages=h.num_pages())
        self._make_plans()
        self._warm_up()
        self._steady_start()

    def _make_plans(self) -> None:
        tr, W = self.tr, self.tr["workflows"]
        prompts = T.quantiles(tr["prompt_tokens"],
                              tr["prompt_tokens"]["distinct"])
        self.prompt_lengths = sorted({int(round(p)) for p in prompts})
        if len(self.prompt_lengths) % W:
            raise ValueError("the distinct prompt lengths must split "
                             "evenly over the workflows")
        perm = T.rng(SCHEDULE, "prompt-sets").permutation(
            len(self.prompt_lengths))
        per = len(self.prompt_lengths) // W
        specs = {"reasoning": tr["reasoning_tokens"],
                 "reason_greedy": tr["greedy"]["reasoning"],
                 "trigger": tr["trigger_every"],
                 "fork_len": tr["fork_tokens"],
                 "fork_greedy": tr["greedy"]["fork"],
                 "terminate": tr["terminate"]}
        self.flows = []
        for w in range(W):
            mine = [self.prompt_lengths[i]
                    for i in perm[w * per:(w + 1) * per]]
            streams = {name: T.stream(spec, SCHEDULE, f"{name}-{w}")
                       for name, spec in specs.items()}
            # each workflow cycles through its own prompt lengths, so
            # the window needs only the shapes set-up compiled
            streams["prompt"] = T.Stream(
                lambda k, mine=mine, w=w: [mine[i] for i in T.rng(
                    SCHEDULE, f"prompt-{w}-{k}").permutation(per)])
            self.flows.append(Workflow(w, streams))
        self.tokens_rng = T.rng(self.seed, "tokens")
        self.seed_rng = T.rng(self.seed, "row-seeds")

    def _warm_up(self) -> None:
        """Compile every shape the window can use, and only those: the
        decode step at max_batch, the suffix prefill and page write-back
        of every prompt length of this cell (admission), the page-pool
        scatters at each power-of-two width a step can need, and the
        one-page read the store's streamed migration uses.  Warm-up rows
        are cancelled and store no prefix.  The contexts the window
        opens on are admitted in set-up itself (``_steady_start``)."""
        eng, pool, loop = self.eng, self.eng.pool, self.loop
        eng.store_prefixes = False
        g = T.rng(self.seed, "warm-up")
        ids = [eng.submit(list(g.integers(0, self.cfg["vocab_size"], n)),
                          max_new_tokens=2, temperature=0.0)
               for n in self.prompt_lengths]
        eng.kick()
        loop.run(stop=lambda: all(
            eng.generation(i).status != "pending" for i in ids))
        for i in ids:
            eng.cancel(i)
        loop.run(stop=eng.pump_idle)
        eng.store_prefixes = True
        # A decode step scrubs at most two reused pages a row (a fresh
        # page and a copy-on-write target) and copies at most one page a
        # row; an admission scrubs at most a row's block table.  The
        # pool pads each to the next power of two.  Reused pages are
        # made by an alloc and release; copies go past the arena and
        # drop, so the cache is unchanged.
        batch = self.tr["max_batch"]
        top_scrub = _pow2(max(2 * batch, pool.pages_per_row))
        top_copy = _pow2(batch)
        w = 1
        while w <= top_scrub:
            pages = pool.alloc(w)
            eng._cache = pool.flush_scrub(eng._cache)
            pool.release(pages)
            pages = pool.alloc(w)         # the same pages, now reused
            eng._cache = pool.flush_scrub(eng._cache)
            pool.release(pages)
            if w <= top_copy:
                eng._cache = pool.copy_pages(eng._cache, [0] * w,
                                             [pool.num_pages] * w)
            w *= 2
        pool.read_pages(eng._cache, [0])
        jax.block_until_ready(eng._cache)

    def _steady_start(self) -> None:
        """Open the window on the closed loop's steady state.  The loop's
        length process is run on the host first: every workflow starts
        an iteration at step 0, and each decode step gives every live row
        one token, by the same rules and from the same streams as the
        window's traffic, for as many steps as ``STEADY_ITERATIONS``
        median reasoning rows take.  Each workflow's reasoning row is
        then admitted with the context it has built (its prompt and the
        reasoning decoded so far) and the reasoning it has left, and each
        fork still live is forked off it with the tokens it has left: at
        the row's current context, since the engine forks only there,
        not at the fork's own trigger point.
        The simulation's streams go on in the window."""
        self._simulate(STEADY_ITERATIONS * int(
            self.tr["reasoning_tokens"]["median"]))
        ctxs = [self._tokens(wf.prompt + wf.reason_tokens)
                for wf in self.flows]
        self._admit_prefixes(ctxs)
        for wf, ctx in zip(self.flows, ctxs):
            self._submit_reason(wf, ctx)
        self.loop.run(stop=lambda: all(
            self.eng.generation(wf.reason).status != "pending"
            for wf in self.flows))
        for wf in self.flows:
            # forked at one context, so at most one of them is greedy
            forks, greedy = [], False
            for g, left in wf.sim_forks:
                forks.append((g and not greedy, left))
                greedy = greedy or g
            self._fork(wf, wf.iteration, forks)
            wf.sim_forks = []

    def _simulate(self, steps: int) -> None:
        for wf in self.flows:
            self._next_iteration(wf)
        for _ in range(steps):
            for wf in self.flows:
                fork = self._trigger(wf, len(wf.sim_forks))
                end = wf.reason_tokens >= wf.length
                live = []
                for f in wf.sim_forks:
                    f[1] -= 1
                    if f[1] > 0:
                        live.append(f)
                    elif wf.take("terminate"):
                        end = True
                wf.sim_forks = live
                if end:
                    self._next_iteration(wf)
                elif fork:
                    wf.sim_forks += [list(f) for f in self._pair(wf)]

    def _admit_prefixes(self, ctxs: List[List[int]]) -> None:
        """Contexts longer than the prompts' widest prefill bucket are
        admitted through prefixes of themselves: each prefix row is
        admitted (admission parks its prefix in the store) and
        cancelled, and the next, longer one finds that prefix in the
        store and prefills only the rest.  So set-up prefills no wider
        bucket than the prompts do."""
        chunk = _pow2(int(self.tr["prompt_tokens"]["hi"]) - 1)
        cuts = []
        for c in ctxs:
            n = len(c) - 1                # decode consumes the last token
            k = -(-n // chunk)
            cuts.append([round(i * n / k) for i in range(1, k)])
        eng = self.eng
        for level in range(max(map(len, cuts), default=0)):
            ids = [eng.submit(c[:cut[level] + 1], max_new_tokens=1,
                              temperature=0.0)
                   for c, cut in zip(ctxs, cuts) if level < len(cut)]
            eng.kick()
            self.loop.run(stop=lambda: all(
                eng.generation(i).status != "pending" for i in ids))
            for i in ids:
                eng.cancel(i)

    # ------------------------------------------------------------ traffic
    def _tokens(self, n: int) -> List[int]:
        return [int(t) for t in self.tokens_rng.integers(
            0, self.cfg["vocab_size"], n)]

    def _row_seed(self) -> int:
        return int(self.seed_rng.integers(0, 2 ** 31))

    def _next_iteration(self, wf: Workflow) -> None:
        wf.iteration += 1
        wf.prompt = wf.take("prompt")
        wf.length = wf.take("reasoning")
        wf.greedy = bool(wf.take("reason_greedy"))
        wf.reason_tokens = 0
        wf.next_trigger = wf.take("trigger")

    def _trigger(self, wf: Workflow, live_forks: int) -> bool:
        """Count one reasoning token; True where it is a trigger point
        and the workflow has room for a pair."""
        wf.reason_tokens += 1
        if wf.reason_tokens < wf.next_trigger:
            return False
        wf.next_trigger += wf.take("trigger")
        return live_forks + self.tr["forks_per_trigger"] <= \
            self.tr["max_live_forks"]

    def _pair(self, wf: Workflow) -> List[tuple]:
        """(greedy, tokens) of each fork a trigger starts: only the
        first fork of a sampled reasoning row may decode greedily."""
        out = []
        for j in range(self.tr["forks_per_trigger"]):
            greedy = j == 0 and not wf.greedy and \
                bool(wf.take("fork_greedy"))
            out.append((greedy, wf.take("fork_len")))
        return out

    def _temperature(self, greedy: bool, kind: str) -> float:
        return 0.0 if greedy else self.tr["temperature"][kind]

    def _start_iteration(self, wf: Workflow) -> None:
        self._next_iteration(wf)
        self._submit_reason(wf, self._tokens(wf.prompt))

    def _submit_reason(self, wf: Workflow, tokens: List[int]) -> None:
        with TraceAnnotation("bench.traffic.submit"):
            gid = self.eng.submit(
                tokens, max_new_tokens=wf.length - wf.reason_tokens,
                temperature=self._temperature(wf.greedy, "reasoning"),
                reasoning=True, seed=self._row_seed())
            self._note_row(gid, "reasoning", wf.greedy, None)
            wf.reason, wf.forks = gid, set()
            it = wf.iteration
            self.eng.subscribe(
                gid, on_token=lambda g, t, wf=wf: self._on_reason_token(
                    wf, g, t),
                on_done=lambda g, wf=wf, it=it: self._end_soon(wf, it))
            self.iterations_started += self.in_window
            self.eng.kick()

    def _note_row(self, gid: int, kind: str, greedy: bool, parent) -> None:
        self.rows[gid] = {"kind": kind, "greedy": greedy, "parent": parent,
                          "in_window": self.in_window}
        self.times[gid] = []
        self.attempted += self.in_window

    def _stamp(self, g) -> None:
        self.times[g.gen_id].append(time.perf_counter())
        if self.record_steps:
            # the step that produced this token attended over g.pos keys
            self.step_ctx.setdefault(self.eng.decode_dispatches,
                                     []).append(g.pos)

    def _on_reason_token(self, wf: Workflow, g, _tok) -> None:
        self._stamp(g)
        if self._trigger(wf, len(wf.forks)):
            it = wf.iteration
            self.loop.schedule(0.0, lambda: self._fork(wf, it),
                               tag="bench-fork")

    def _fork(self, wf: Workflow, it: int,
              forks: Optional[List[tuple]] = None) -> None:
        """Fork off the workflow's reasoning row: a new pair, or the
        ``forks`` given as (greedy, tokens left)."""
        if wf.iteration != it or \
                self.eng.generation(wf.reason).status != "running":
            return
        if forks is None:
            forks = self._pair(wf)
        with TraceAnnotation("bench.traffic.fork"):
            for greedy, left in forks:
                fid = self.eng.fork(
                    wf.reason, max_new_tokens=left,
                    temperature=self._temperature(greedy, "fork"),
                    seed=self._row_seed())
                self._note_row(fid, "fork", greedy, wf.reason)
                wf.forks.add(fid)
                self.forks_started += self.in_window
                self.eng.subscribe(
                    fid, on_token=lambda g, t: self._stamp(g),
                    on_done=lambda g, wf=wf, it=it: self._fork_done(
                        wf, it, g))
            self.eng.kick()

    def _fork_done(self, wf: Workflow, it: int, g) -> None:
        wf.forks.discard(g.gen_id)
        self.forks_finished += self.in_window
        if wf.iteration == it and wf.take("terminate"):
            self._end_soon(wf, it)

    def _end_soon(self, wf: Workflow, it: int) -> None:
        self.loop.schedule(0.0, lambda: self._end_iteration(wf, it),
                           tag="bench-end")

    def _end_iteration(self, wf: Workflow, it: int) -> None:
        if wf.iteration != it:
            return
        with TraceAnnotation("bench.traffic.cancel"):
            for gid in [wf.reason, *wf.forks]:
                self.eng.cancel(gid)
        self._start_iteration(wf)

    # ------------------------------------------------------------ window
    def counters(self) -> dict:
        e, pool = self.eng, self.eng.pool
        return {"tokens_decoded": e.tokens_decoded,
                "decode_dispatches": e.decode_dispatches,
                "suffix_prefill_dispatches": e.suffix_prefill_dispatches,
                "suffix_prefill_rows": e.suffix_prefill_rows,
                "tokens_prefilled": e.tokens_prefilled,
                "page_copies": pool.page_copies,
                "store_migrations": e.store.stats.migrations,
                "store_evictions": e.store.stats.evictions_local,
                "pages_in_use": pool.pages_in_use,
                "forks_started": self.forks_started,
                "forks_finished": self.forks_finished,
                "iterations_started": self.iterations_started}

    def run_window(self, window) -> None:
        self.in_window = True
        with TraceAnnotation("bench.loop"):
            self.loop.run(stop=window.poll)
        self.in_window = False

    def end_to_end(self, t0: float, t1: float, c0: dict, c1: dict) -> dict:
        gaps = []
        for ts in self.times.values():
            inside = [t for t in ts if t0 <= t <= t1]
            gaps += [b - a for a, b in zip(inside, inside[1:])]
        return {
            "decode_tok_s": (c1["tokens_decoded"] - c0["tokens_decoded"])
            / (t1 - t0),
            # the tail of every gap in the window, not of chunk medians
            "itl_p95_ms": float(np.percentile(gaps, 95)) * 1e3,
            "_itl_samples": len(gaps),
        }

    def step_contexts(self, k0: int, k1: int) -> List[List[int]]:
        """Context lengths (keys attended) of the rows each decode step
        in (k0, k1] advanced, as the host saw their tokens arrive."""
        return [self.step_ctx[k] for k in range(k0 + 1, k1 + 1)
                if k in self.step_ctx]

    # ------------------------------------------------------------ check
    def release(self) -> None:
        """Drop the engine (its page arena) before the reference runs;
        the weights stay for it."""
        self.finished = {gid: (list(self.eng.generation(gid).tokens),
                               self.eng.generation(gid).prompt_len)
                         for gid in self.rows}
        del self.eng, self.plane, self.loop
        import gc
        gc.collect()

    def sample_rows(self) -> List[int]:
        """Greedy rows to compare: the one with the longest context, the
        greedy fork that served most tokens, then rows drawn from the
        seed until the check budget of rows is met."""
        chk = self.tr["check"]
        cands = [gid for gid, r in self.rows.items() if r["greedy"]
                 and len(self.finished[gid][0]) - self.finished[gid][1]
                 >= chk["min_tokens"]]
        if not cands:
            return []
        served = {gid: len(self.finished[gid][0]) - self.finished[gid][1]
                  for gid in cands}
        pick = [max(cands, key=lambda i: (len(self.finished[i][0]), i))]
        forks = [i for i in cands if self.rows[i]["kind"] == "fork"
                 and i not in pick]
        if forks:
            pick.append(max(forks, key=lambda i: (served[i], i)))
        rest = sorted(set(cands) - set(pick))
        g = T.rng(self.seed, "check-sample")
        for i in g.permutation(len(rest)):
            if len(pick) >= chk["rows"]:
                break
            pick.append(rest[i])
        return pick

    def check(self, reference, control: bool = False) -> dict:
        """Compare the sampled greedy rows with the reference: for every
        token the program served, its gap, how far its reference logit
        lies below the reference's best.  ``widest_gap`` is the largest,
        ``mean_gap`` the mean over all served tokens.  With ``control``
        the float8 control takes the program's place: at each of those
        positions the token it puts first is scored instead, so these
        numbers are the control's, and the program's own are kept under
        ``program_``."""
        pick = self.sample_rows()
        gaps, own = [], []
        for gid in pick:
            toks, start = self.finished[gid]
            gaps.append(reference.token_gaps(self.cfg, self.params, toks,
                                             start, control=control))
            if control:
                own.append(reference.token_gaps(self.cfg, self.params,
                                                toks, start))
        out = {"rows_checked": len(pick), **_gap_stats(gaps, "")}
        if control:
            out.update(_gap_stats(own, "program_"))
        return out


def _gap_stats(parts, prefix: str) -> dict:
    g = np.concatenate(parts) if parts else np.zeros((0,), np.float32)
    return {f"{prefix}widest_gap": float(g.max()) if len(g) else 0.0,
            f"{prefix}mean_gap": float(g.mean()) if len(g) else 0.0,
            f"{prefix}mismatch_share": float((g > 0).mean()) if len(g)
            else 0.0,
            f"{prefix}tokens_checked": int(len(g))}
