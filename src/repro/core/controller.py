"""SpecController (paper Algorithm 1) on the discrete-event loop.

The controller wraps a user-specified LLM backend, prompt/search
algorithm and termination criterion (paper §5 step 1: SpecGen requires
no changes to the underlying LLM or search algorithm).  Per iteration:

  * start the main reasoning generation and stream its trace,
  * parse trigger signals (``core.triggers``) — or fork on idle devices,
  * fork K = max(1, min(C.val, C.prof)) non-reasoning speculative
    generations conditioned on the reasoning prefix (prefix KV reuse via
    the two-tier store => near-zero re-prefill token cost), throttled by
    the scheduler's backpressure signal (``sched.pressure``),
  * submit emitted kernels to the ElasticScheduler as DEFERRED requests:
    the evaluation thunk runs when a device is granted (real mode: the
    kernel build overlaps the still-streaming reasoning
    generation) and the EvalFuture resolves at completion; fallback
    kernels carry PRIO_FALLBACK and outrank queued speculative ones,
  * early-terminate the reasoning generation when a speculative kernel
    meets the termination criterion (default: historical mean speedup),
  * at the iteration boundary abort in-flight work, update the search
    algorithm state, and continue.

The controller is continuation-style (no nested event-loop runs), so
many controllers can share one EventLoop + ElasticScheduler pool — the
paper's evaluation setting (10 agent workflows, one device pool).

Token accounting follows §8.7: reasoning tokens are prorated at early
termination; speculative prompt tokens hit the prefix cache and only
the un-cached suffix is charged.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict, List, Optional, Protocol, Tuple

from repro.core.clock import EventLoop
from repro.core.metrics import COUNT_BOUNDS as _COUNT_BOUNDS
from repro.core.scheduler import ElasticScheduler
from repro.core.termination import get_criterion
from repro.core.triggers import StreamTriggerParser
from repro.core.types import (PRIO_FALLBACK, PRIO_SPEC, EvalFuture,
                              GenerationBackend, IterationRecord,
                              KernelCandidate, ProfileResult,
                              ReasoningHandle, SpecHandle,
                              ValidationResult, make_eval_request)


# ------------------------------------------------------------- protocols
@dataclasses.dataclass
class ReasoningScript:
    """A reasoning generation as the controller consumes it."""
    duration: float
    total_tokens: int
    chunks: List[Tuple[float, str]]          # (rel_time, text)
    candidate_fn: Callable[[], Optional[KernelCandidate]]


@dataclasses.dataclass
class SpecScript:
    """A speculative (non-reasoning) generation."""
    duration: float
    tokens: int                              # output tokens
    prompt_tokens: int                       # reasoning-prefix tokens
    candidate: Optional[KernelCandidate]


class LLMBackend(Protocol):
    def reasoning(self, task_id: str, iteration: int,
                  ctx: Dict[str, Any]) -> ReasoningScript: ...
    def speculative(self, task_id: str, iteration: int, ctx: Dict[str, Any],
                    prefix_frac: float) -> SpecScript: ...


# -------------------------------------------------- scripted generation
# GenerationBackend (core/types.py) adapter over any scripted
# LLMBackend.  This IS the pre-refactor controller behavior, factored
# out: chunks replay as loop events at their scripted relative times,
# completion fires at ``script.duration``, a fork's completion at
# ``spec.duration`` (+ the re-prefill estimate when the prefix cache is
# off).  Scheduling order and float expressions are preserved exactly —
# the PR-5 goldens pin this path byte-for-byte.

class _ScriptedReasoning:
    """ReasoningHandle replaying a ReasoningScript's chunk events."""

    def __init__(self, loop: EventLoop, script: ReasoningScript,
                 on_chunk: Callable[[str], None],
                 on_done: Callable[..., None]):
        self.loop, self.script = loop, script
        self.total_tokens = script.total_tokens
        self.chars_total = max(sum(len(c) for _, c in script.chunks), 1)
        self.chars_seen = 0
        self._t0 = loop.now
        self._cancelled = False
        self._events = []

        def fire(text: str) -> None:
            if self._cancelled:
                return
            self.chars_seen += len(text)
            on_chunk(text)

        for rel_t, text in script.chunks:
            self._events.append(
                loop.schedule(rel_t, lambda x=text: fire(x), tag="chunk"))
        self._events.append(
            loop.schedule(script.duration,
                          lambda: on_done(script.total_tokens,
                                          script.duration,
                                          script.candidate_fn),
                          tag="reason-done"))

    def progress(self) -> float:
        return min(1.0, self.chars_seen / self.chars_total)

    def consumed_tokens(self) -> float:
        consumed = min(1.0, (self.loop.now - self._t0)
                       / max(self.script.duration, 1e-9))
        return consumed * self.script.total_tokens

    def cancel(self) -> None:
        self._cancelled = True
        for ev in self._events:
            ev.cancel()


class _ScriptedSpec:
    """SpecHandle whose completion is one scheduled loop event."""

    def __init__(self, loop: EventLoop, spec: SpecScript):
        self.loop, self.spec = loop, spec
        self.prompt_tokens = spec.prompt_tokens
        self._event = None

    def launch(self, extra_delay: float,
               on_done: Callable[[int, Optional[KernelCandidate]],
                                 None]) -> None:
        s = self.spec
        # the script belongs to the backend (it may be shared/cached):
        # the re-prefill delay is added locally, never written back
        self._event = self.loop.schedule(
            s.duration + extra_delay,
            lambda: on_done(s.tokens, s.candidate), tag="spec")

    def cancel(self) -> None:
        if self._event is not None:
            self._event.cancel()


class ScriptedGeneration:
    """GenerationBackend over a scripted LLMBackend (the sim path).

    ``SpecController`` auto-wraps any plain LLMBackend in this adapter,
    so pre-protocol call sites keep working unchanged."""

    def __init__(self, llm: LLMBackend, loop: EventLoop):
        self.llm, self.loop = llm, loop

    def begin_reasoning(self, task_id: str, iteration: int,
                        ctx: Dict[str, Any], *,
                        on_chunk: Callable[[str], None],
                        on_done: Callable[..., None]) -> _ScriptedReasoning:
        script = self.llm.reasoning(task_id, iteration, ctx)
        return _ScriptedReasoning(self.loop, script, on_chunk, on_done)

    def fork(self, task_id: str, iteration: int, ctx: Dict[str, Any],
             prefix_frac: float) -> _ScriptedSpec:
        spec = self.llm.speculative(task_id, iteration, ctx, prefix_frac)
        return _ScriptedSpec(self.loop, spec)


class EvalBackend(Protocol):
    """Synchronous evaluation: returns (latency, result) when called.

    The controller never calls these eagerly — they are wrapped into
    deferred thunks (``submit_validate`` below) that run when the
    scheduler grants a device."""
    def validate(self, cand: KernelCandidate
                 ) -> Tuple[float, ValidationResult]: ...
    def profile(self, cand: KernelCandidate
                ) -> Tuple[float, ProfileResult]: ...


class AsyncEvalBackend(Protocol):
    """Deferred evaluation: submit_* package the work as a Request whose
    thunk executes at device dispatch; the returned EvalFuture resolves
    when the scheduler completes the request.  Backends implement this
    directly when submission itself has cross-request structure (the
    real backend batches same-shape builds co-resident in a queue)."""
    def submit_validate(self, cand: KernelCandidate) -> EvalFuture: ...
    def submit_profile(self, cand: KernelCandidate) -> EvalFuture: ...


def submit_validate(evaluator, cand: KernelCandidate) -> EvalFuture:
    """Deferred validation via the backend's async protocol, or by
    wrapping a synchronous backend's ``validate`` into a dispatch-time
    thunk."""
    sub = getattr(evaluator, "submit_validate", None)
    if sub is not None:
        return sub(cand)
    return make_eval_request("validation", cand,
                             lambda: evaluator.validate(cand))


def submit_profile(evaluator, cand: KernelCandidate) -> EvalFuture:
    sub = getattr(evaluator, "submit_profile", None)
    if sub is not None:
        return sub(cand)
    return make_eval_request("profiling", cand,
                             lambda: evaluator.profile(cand))


class SearchAlgorithm(Protocol):
    def init_ctx(self, task_id: str) -> Dict[str, Any]: ...
    def update(self, ctx: Dict[str, Any], best: Optional[KernelCandidate],
               feedback: List[ProfileResult]) -> Dict[str, Any]: ...


@dataclasses.dataclass
class SpecGenConfig:
    iterations: int = 100
    termination: Any = "hist-avg"
    enable_speculation: bool = True          # ablation: off => baseline
    idle_fork: bool = True                   # fork when pool idles (§6.1.1)
    idle_probe_interval: float = 110.0
    max_concurrent_spec: int = 2             # serving-capacity bound
    prefix_cache: bool = True                # remote KV reuse (§6.2.3)
    min_prefix_frac: float = 0.05            # don't fork on empty traces


@dataclasses.dataclass
class TaskResult:
    task_id: str
    records: List[IterationRecord]
    best_speedup: float
    best_candidate: Optional[KernelCandidate]
    total_tokens: float
    reasoning_tokens: float
    spec_tokens: float
    cached_prefix_tokens: float
    e2e_time: float
    profiling_feedback: int
    early_terminations: int
    history: List[float]
    # remote-KV transport accounting (0 without a TransportPlane): how
    # many fork-prefix fetches rode the modeled link, and their total
    # modeled latency — the fetch cost prefix-store hits now carry
    prefix_fetches: int = 0
    prefix_fetch_s: float = 0.0


class SpecController:
    def __init__(self, loop: EventLoop, scheduler: ElasticScheduler,
                 llm: LLMBackend, evaluator: EvalBackend,
                 search: SearchAlgorithm, cfg: SpecGenConfig,
                 name: str = "w0", transport=None,
                 tenant: str = "", deadline_s: float = math.inf):
        self.loop, self.sched = loop, scheduler
        # traffic plane (DESIGN.md §Traffic-plane): the owning tenant
        # and the workflow-relative SLO deadline.  Defaults ("" / inf)
        # keep every closed-loop caller — and the golden traces —
        # byte-identical: the stamps below become the Request field
        # defaults and the SLO heap-key layer is off.
        self.tenant = tenant
        self.deadline_s = deadline_s
        self.deadline = math.inf
        # generations run through the GenerationBackend seam; a plain
        # scripted LLMBackend is auto-wrapped so existing call sites
        # (and the byte-pinned sim path) are unchanged
        if not hasattr(llm, "begin_reasoning"):
            llm = ScriptedGeneration(llm, loop)
        self.gen: GenerationBackend = llm
        self.llm = getattr(llm, "llm", llm)  # underlying scripted backend
        self.evaluator, self.search = evaluator, search
        self.cfg = cfg
        self.name = name
        # remote-KV transport plane (serving/transport.py): when set,
        # prefix-store hits are no longer free — each speculative fork
        # fetches its reasoning-prefix KV over the modeled link and the
        # fetch latency lands in the fork's availability time
        self.transport = transport
        if transport is not None:
            assert transport.loop is loop, \
                "transport plane must share the controller's event loop"
        self.criterion = get_criterion(cfg.termination)
        self.gen_timeline: List[tuple] = []     # (t, reasoning+spec inflight)
        self.done = False
        self.result: Optional[TaskResult] = None
        self._on_done: Optional[Callable[["SpecController"], None]] = None

    # ------------------------------------------------------------ main API
    def run_task(self, task_id: str) -> TaskResult:
        """Single-workflow convenience: start + drive the loop."""
        self.start(task_id)
        self.loop.run(stop=lambda: self.done)
        assert self.result is not None
        return self.result

    def start(self, task_id: str,
              on_done: Optional[Callable[["SpecController"], None]] = None
              ) -> None:
        self._on_done = on_done
        self._task_id = task_id
        self._ctx = self.search.init_ctx(task_id)
        self._history: List[float] = [0.0]        # H <- {0} (Alg 1 line 1)
        self._best: Optional[KernelCandidate] = None
        self._best_speedup = 0.0
        self._records: List[IterationRecord] = []
        self._tok = {"reason": 0.0, "spec": 0.0, "cached": 0.0}
        self._fetch = {"n": 0, "s": 0.0}
        self._early_terms = 0
        self._feedback_total = 0
        self._t0 = self.loop.now
        # absolute SLO deadline: workflow-relative budget anchored at
        # start time — the EDF key every eval request below carries
        self.deadline = self._t0 + self.deadline_s
        # causal root (§Observability): everything this workflow causes
        # — generations, forks, evals, transfers — parents up to here
        self._wspan = self.loop.spans.begin(
            "gen", "workflow", f"{self.name}:{task_id}")
        # schedule the first iteration as an event so multiple controllers
        # can be started before the loop runs
        self.loop.schedule(0.0, lambda: self._begin_iteration(0))

    # -------------------------------------------------------- one iteration
    def _begin_iteration(self, it: int) -> None:
        if it >= self.cfg.iterations:
            self._finalize()
            return
        rec = IterationRecord(index=it, t_start=self.loop.now)
        self.sched.begin_iteration(it)
        # composed timeline: the reasoning generation opens the "gen"
        # plane for this workflow (closed at reason-done / termination)
        self.loop.record("gen", "start", f"{self.name}:{it}")
        task_id, ctx = self._task_id, self._ctx
        parser = StreamTriggerParser()
        state = {
            "it": it, "rec": rec, "handle": None, "parser": parser,
            "done": False, "reason_done": False, "terminated": False,
            "gen_closed": False,
            "spec_live": 0, "spec_handles": [], "probe_events": [],
            "fallback_pending": False, "best": None,
            "t_gen_start": self.loop.now,
            # causal spans: the reasoning-generation span (closed with
            # the ("gen","end") record by _close_gen) and the sids of
            # forks still in flight (closed at spec-done, or with
            # status "cancel" when the iteration tears them down)
            "span": self.loop.spans.begin("gen", "gen",
                                          f"{self.name}:{it}",
                                          parent=self._wspan),
            "fork_open": [],
        }

        def on_chunk(text):
            if state["done"] or state["terminated"]:
                return
            triggers = parser.feed(text)
            if self.cfg.enable_speculation and triggers:
                self._fork(state)

        def on_reason_complete(total_tokens, duration, candidate_fn):
            if state["done"] or state["terminated"]:
                return
            state["reason_done"] = True
            self._close_gen(state, f"{self.name}:{it}")
            rec.gen_time += duration
            self._tok["reason"] += total_tokens
            rec.reasoning_tokens += total_tokens
            cand = candidate_fn()
            if cand is not None:
                cand.iteration = it
                cand.origin = "reasoning"
                cand.prefix_frac = 1.0
                rec.candidates += 1
                state["fallback_pending"] = True
                self._submit_validation(cand, state, fallback=True)
            else:
                self._maybe_finish(state)

        # the backend parents whatever it opens (the engine backend's
        # decode row) under this iteration's gen span via the cursor
        self.loop.spans.push_parent(state["span"])
        state["handle"] = self.gen.begin_reasoning(
            task_id, it, ctx, on_chunk=on_chunk,
            on_done=on_reason_complete)
        self.loop.spans.pop_parent()

        # idle-fork probe (Alg 1 line 7: "... or GPU is idle")
        if self.cfg.enable_speculation and self.cfg.idle_fork:
            def idle_probe():
                if state["done"] or state["terminated"] or \
                        state["reason_done"]:
                    return
                if (self.sched.idle_val > 0 or self.sched.idle_prof > 0) \
                        and state["spec_live"] < self.cfg.max_concurrent_spec:
                    self._fork(state)
                state["probe_events"].append(
                    self.loop.schedule(self.cfg.idle_probe_interval,
                                       idle_probe, tag="idle-probe"))
            state["probe_events"].append(
                self.loop.schedule(self.cfg.idle_probe_interval, idle_probe,
                                   tag="idle-probe"))

    # ----------------------------------------------------------- fork logic
    def _fork(self, state) -> None:
        if state["terminated"] or state["reason_done"] or state["done"]:
            return
        # K = max(1, min(C.val, C.prof)) (Alg 1 line 10), where capacity
        # is the currently *idle* split — "enough candidates to keep GPUs
        # busy without overloading the queues" (§6.1.1).  Under queue
        # pressure (shared pool, bursty arrivals) forking pauses.
        if self.sched.pressure >= 1.0:
            return
        cval = max(self.sched.idle_val, 1 if self.sched.idle_prof else 0)
        cprof = max(self.sched.idle_prof, 1 if self.sched.idle_val else 0)
        k = max(1, min(cval, cprof)) if (cval or cprof) else 1
        k = min(k, self.cfg.max_concurrent_spec - state["spec_live"])
        if k <= 0:
            return
        frac = state["handle"].progress()
        if frac < self.cfg.min_prefix_frac:
            return
        it, rec = state["it"], state["rec"]
        for _ in range(k):
            # fork span opens BEFORE the backend call so the engine
            # backend's forked decode row parents under it; a declined
            # fork closes it immediately with status "declined".  The
            # .get() fallbacks (here and below) tolerate the minimal
            # hand-built states tests drive _fork with directly.
            fork_sid = self.loop.spans.begin(
                "gen", "fork", f"{self.name}:{it}",
                parent=state.get("span", -1))
            self.loop.spans.push_parent(fork_sid)
            h = self.gen.fork(self._task_id, it, self._ctx, frac)
            self.loop.spans.pop_parent()
            if h is None:
                # the serving substrate declined (no free slot / parent
                # not decoding) — skip this speculative slot
                self.loop.spans.end(fork_sid, status="declined")
                continue
            state["spec_live"] += 1
            state.setdefault("fork_open", []).append(fork_sid)
            self.loop.record("gen", "fork", f"{self.name}:{it}")
            self.loop.metrics.histogram("fork_depth", _COUNT_BOUNDS) \
                .observe(float(state["spec_live"]))
            self._mark_gen(state)
            # prefix-cache accounting (paper §6.2.3): fork prompt KV is
            # shared with the live reasoning generation; without the
            # remote cache the fork re-prefills its prompt (token cost
            # AND latency at the serving prefill rate, added at launch).
            extra_delay = 0.0
            xfer = None
            if self.cfg.prefix_cache:
                self._tok["cached"] += h.prompt_tokens
                rec.cached_prefix_tokens += h.prompt_tokens
                if self.transport is not None:
                    # the prefix hit is served from the REMOTE tier over
                    # the modeled link.  The transfer rides the shared
                    # serial wire (utilization traces; it queues behind
                    # migrations), and the fork's candidate becomes
                    # available only once the prefix KV has ACTUALLY
                    # landed — the queued completion below, not the
                    # queue-free estimate.
                    self.loop.spans.push_parent(fork_sid)
                    _lat, xfer = self.transport.prefix_fetch(
                        h.prompt_tokens, tag=f"prefix-{self.name}")
                    self.loop.spans.pop_parent()
                    self._fetch["n"] += 1

                    def account(_f, x=xfer):
                        self._fetch["s"] += x.finished - x.submitted
                    xfer.future.add_done_callback(account)
            else:
                self._tok["spec"] += h.prompt_tokens
                rec.spec_tokens += h.prompt_tokens
                extra_delay = h.prompt_tokens / 2500.0

            def on_spec_done(tokens, candidate, x=xfer, sid=fork_sid):
                if x is not None and not x.done and \
                        not (state["done"] or state["terminated"]):
                    # the generation finished but its prefix KV is still
                    # on the wire: availability waits for the tail (the
                    # continuation re-checks the iteration state — a
                    # terminated iteration ignores the late landing)
                    x.future.add_done_callback(
                        lambda _f: None
                        if (state["done"] or state["terminated"])
                        else on_spec_done(tokens, candidate, None))
                    return
                state["spec_live"] -= 1
                if sid in state.get("fork_open", ()):
                    state["fork_open"].remove(sid)
                    self.loop.spans.end(sid)
                self._mark_gen(state)
                if state["done"] or state["terminated"]:
                    return
                self._tok["spec"] += tokens
                rec.spec_tokens += tokens
                if candidate is not None:
                    candidate.iteration = it
                    rec.candidates += 1
                    self._submit_validation(candidate, state,
                                            fallback=False)
            h.launch(extra_delay, on_spec_done)
            state["spec_handles"].append(h)

    # ------------------------------------------------- validation/profiling
    # Deferred execution: submission only QUEUES a thunk — the kernel
    # build / latency draw happens when the scheduler grants a device
    # (Request.thunk inside _start), and the EvalFuture resolves at the
    # completion event.  Aborted requests' futures are cancelled by the
    # scheduler, so the callbacks below never see aborted work.
    def _submit_validation(self, cand, state, fallback: bool) -> None:
        rec = state["rec"]
        fut = submit_validate(self.evaluator, cand)
        req = fut.request
        req.owner = self.name
        req.tenant = self.tenant
        req.deadline = self.deadline
        req.priority = PRIO_FALLBACK if fallback else PRIO_SPEC
        # eval span: open at SUBMIT (queue wait is part of the span);
        # the scheduler closes it at complete or abort — either path,
        # including queued-at-iteration-boundary aborts
        req.span = self.loop.spans.begin(
            "eval", "eval", f"validation:{self.name}",
            parent=state.get("span", -1))

        def done(f: EvalFuture):
            if state["done"]:
                return
            res: ValidationResult = f.value
            if res.ok:
                rec.validated += 1
                self._submit_profile(cand, state, fallback)
            else:
                rec.status = res.failure or "invalid"
                if fallback:
                    state["fallback_pending"] = False
                    self._maybe_finish(state)
        fut.add_done_callback(done)
        self.sched.submit(req)

    def _submit_profile(self, cand, state, fallback: bool) -> None:
        rec = state["rec"]
        fut = submit_profile(self.evaluator, cand)
        req = fut.request
        req.owner = self.name
        req.tenant = self.tenant
        req.deadline = self.deadline
        req.priority = PRIO_FALLBACK if fallback else PRIO_SPEC
        req.span = self.loop.spans.begin(
            "eval", "eval", f"profiling:{self.name}",
            parent=state.get("span", -1))

        def done(f: EvalFuture):
            if state["done"]:
                return
            res: ProfileResult = f.value
            rec.profiled += 1
            rec.status = "success"
            speedup = res.speedup
            prior = list(self._history)            # H before this kernel
            self._history.append(speedup)
            if state["best"] is None or speedup > state["best"][1]:
                state["best"] = (cand, speedup)
            if fallback:
                state["fallback_pending"] = False
                self._maybe_finish(state)
                return
            if not state["terminated"] and self.criterion(prior, speedup):
                self._terminate(state)
        fut.add_done_callback(done)
        self.sched.submit(req)

    # ----------------------------------------------------------- completion
    def _terminate(self, state) -> None:
        """Early termination (Alg 1 lines 17-20).

        Cancelling the reasoning handle is what cuts generation cost:
        on the scripted path it cancels the remaining chunk events; on
        the engine path it cancels REAL in-flight decode (pages
        released, remaining tokens never computed)."""
        rec, handle = state["rec"], state["handle"]
        state["terminated"] = True
        self._close_gen(state, f"{self.name}:{state['it']}:term")
        rec.early_terminated = True
        self._early_terms += 1
        consumed_tokens = handle.consumed_tokens()
        self._tok["reason"] += consumed_tokens
        rec.reasoning_tokens += int(consumed_tokens)
        rec.gen_time += self.loop.now - state["t_gen_start"]
        handle.cancel()
        for h in state["spec_handles"]:
            h.cancel()
        for ev in state["probe_events"]:
            ev.cancel()
        self._close_forks(state, status="cancel")
        self._finish_iteration(state)

    def _maybe_finish(self, state) -> None:
        if state["reason_done"] and not state["fallback_pending"] \
                and not state["done"]:
            for h in state["spec_handles"]:
                h.cancel()
            self._close_forks(state, status="cancel")
            self._finish_iteration(state)

    def _close_forks(self, state, status: str) -> None:
        """Close every fork span still open when the iteration tears
        its speculative generations down — the cancel half of the
        every-span-closes invariant."""
        for sid in state.get("fork_open", ()):
            self.loop.spans.end(sid, status=status)
        state["fork_open"] = []

    def _close_gen(self, state, tag: str) -> None:
        """Close this iteration's "gen" span exactly once.  Termination
        can race reason-completion (the fallback kernel is still in the
        queues when a speculative one meets the criterion); whichever
        path runs first emits the paired ("gen","end") — the other is a
        no-op, so ``plane_breakdown`` never sees an unclosed or
        double-closed generation."""
        if state["gen_closed"]:
            return
        state["gen_closed"] = True
        self.loop.record("gen", "end", tag)
        self.loop.spans.end(state.get("span", -1),
                            status="term" if state["terminated"] else "ok")

    def _finish_iteration(self, state) -> None:
        state["done"] = True
        rec = state["rec"]
        rec.t_end = self.loop.now
        self._records.append(rec)
        self._feedback_total += rec.profiled
        if state["best"] is not None and \
                state["best"][1] > self._best_speedup:
            self._best, self._best_speedup = state["best"]
        rec.best_speedup = self._best_speedup
        self.sched.end_iteration(owner=self.name)
        fb = [ProfileResult(speedup=s) for s in self._history[1:]]
        self._ctx = self.search.update(self._ctx, self._best, fb)
        self.loop.schedule(0.0,
                           lambda: self._begin_iteration(state["it"] + 1))

    def _finalize(self) -> None:
        self.done = True
        self.loop.spans.end(self._wspan)
        self.result = TaskResult(
            task_id=self._task_id, records=self._records,
            best_speedup=self._best_speedup, best_candidate=self._best,
            total_tokens=self._tok["reason"] + self._tok["spec"],
            reasoning_tokens=self._tok["reason"],
            spec_tokens=self._tok["spec"],
            cached_prefix_tokens=self._tok["cached"],
            e2e_time=self.loop.now - self._t0,
            profiling_feedback=self._feedback_total,
            early_terminations=self._early_terms, history=self._history,
            prefix_fetches=self._fetch["n"],
            prefix_fetch_s=self._fetch["s"])
        if self._on_done is not None:
            self._on_done(self)

    def _mark_gen(self, state) -> None:
        self.gen_timeline.append(
            (self.loop.now,
             (0 if state["reason_done"] else 1) + state["spec_live"]))
