"""Async evaluation plane (DESIGN.md §Async-eval-plane).

Covers the deferred-execution refactor end to end:

  * the EventLoop Future primitive,
  * deferred thunks: evaluation work runs at device GRANT, not submit
    (instrumented for both the sim and the REAL backend),
  * fallback-over-speculative priority ordering,
  * continuous arrival-rate pool reallocation convergence,
  * golden-trace determinism: under the PR-2 compat plane (priority
    off, queue-max realloc) the refactor reproduces the scripted-
    workload IterationRecords captured BEFORE the refactor, event for
    event; the new default plane is run-to-run deterministic,
  * RealEvalBackend: no build side-effects before a device grant,
    same-build batching of co-resident requests, and >= 2 builds
    overlapping a live reasoning generation on a 4-device pool,
  * abort semantics: cancelled futures never fire,
  * SpecController._fork does not mutate backend-owned SpecScripts.
"""
import dataclasses
import json
import pathlib
import types

import pytest

from repro.core.clock import EventLoop, Future
from repro.core.controller import (ReasoningScript, SpecController,
                                   SpecGenConfig, SpecScript)
from repro.core.scheduler import ElasticScheduler, SchedulerConfig
from repro.core.types import (PRIO_FALLBACK, PRIO_SPEC, KernelCandidate,
                              Request, make_eval_request)
from repro.search.driver import run_shared_pool, run_specgen
from repro.search.llm_sim import (FeedbackSearch, SimEvalBackend,
                                  SimLLMBackend)
from repro.search.workload import WorkloadModel

GOLDEN = pathlib.Path(__file__).parent / "golden"


def cand(task="T1", **cfg):
    return KernelCandidate(task_id=task, config=dict(cfg))


def req(kind, dur, done=None, owner="", priority=PRIO_SPEC):
    return Request(kind=kind, duration=dur, candidate=cand(),
                   on_complete=done, owner=owner, priority=priority)


def mk(n=2, **kw):
    loop = EventLoop()
    return loop, ElasticScheduler(loop, SchedulerConfig(num_devices=n, **kw))


# ------------------------------------------------------- future primitive
def test_future_resolves_once_and_late_callbacks_fire():
    f = Future()
    got = []
    f.add_done_callback(lambda ff: got.append(ff.value))
    f.resolve(7)
    f.resolve(8)                       # resolve-once: ignored
    assert got == [7] and f.value == 7
    f.add_done_callback(lambda ff: got.append("late"))
    assert got == [7, "late"]          # post-resolution callback fires now


def test_future_cancel_drops_callbacks():
    f = Future()
    got = []
    f.add_done_callback(lambda ff: got.append(1))
    f.cancel()
    f.resolve(1)
    f.add_done_callback(lambda ff: got.append(2))
    assert got == [] and not f.done


# ------------------------------------------------------ deferred execution
class CountingEval(SimEvalBackend):
    """SimEvalBackend that counts when the (deferred) work executes."""

    def __init__(self, model):
        super().__init__(model)
        self.validations = 0
        self.profiles = 0

    def validate(self, c):
        self.validations += 1
        return super().validate(c)

    def profile(self, c):
        self.profiles += 1
        return super().profile(c)


def test_thunk_runs_at_grant_not_submit():
    loop, s = mk(n=2)
    be = CountingEval(WorkloadModel("glm", seed=0))
    # saturate the single validation device so the next request queues
    s.submit(req("validation", 100.0))
    fut = be.submit_validate(cand(task="T1", _valid=True, _speedup=2.0))
    s.submit(fut.request)
    assert be.validations == 0, "evaluation ran at submit time"
    loop.run(until=50.0)
    assert be.validations == 0     # still queued: no grant, no work
    loop.run()
    assert be.validations == 1 and fut.done and fut.value.ok


def test_scheduler_resolves_future_with_thunk_result():
    loop, s = mk(n=2)
    fut = make_eval_request("validation", cand(), lambda: (12.5, "payload"))
    s.submit(fut.request)
    loop.run()
    assert fut.done and fut.value == "payload"
    assert fut.request.duration == 12.5
    assert fut.request.finished == pytest.approx(12.5)


def test_aborted_request_future_never_fires():
    loop, s = mk(n=2)
    fired = []
    futs = [make_eval_request("validation", cand(), lambda: (100.0, "x"))
            for _ in range(3)]
    for f in futs:
        f.add_done_callback(lambda ff: fired.append(ff))
        s.submit(f.request)
    loop.run(until=10.0)
    s.end_iteration()                  # aborts busy + queued
    loop.run()
    assert fired == []
    assert all(f.cancelled for f in futs)
    assert len(s.aborted) == 3


# ------------------------------------------------------- priority ordering
def test_fallback_outranks_queued_spec_requests():
    """A reasoning-fallback request submitted BEFORE newer speculative
    ones is still served first (under pure LAF the newest spec request
    would win)."""
    order = []
    loop, s = mk(n=2, priority=True)
    s.submit(req("validation", 10.0))                   # occupy the device
    s.submit(req("validation", 1.0, priority=PRIO_FALLBACK,
                 done=lambda r: order.append("fallback")))
    for i in range(2):                                  # newer spec arrivals
        s.submit(req("validation", 1.0, priority=PRIO_SPEC,
                     done=lambda r, i=i: order.append(f"spec{i}")))
    loop.run()
    assert order[0] == "fallback"
    assert order[1:] == ["spec1", "spec0"]              # then LAF among spec

    # compat mode: priority off restores pure LAF (newest first)
    order2 = []
    loop2, s2 = mk(n=2, priority=False)
    s2.submit(req("validation", 10.0))
    s2.submit(req("validation", 1.0, priority=PRIO_FALLBACK,
                  done=lambda r: order2.append("fallback")))
    for i in range(2):
        s2.submit(req("validation", 1.0, priority=PRIO_SPEC,
                      done=lambda r, i=i: order2.append(f"spec{i}")))
    loop2.run()
    assert order2 == ["spec1", "spec0", "fallback"]


def test_pressure_is_queued_validations_per_device():
    loop, s = mk(n=2)
    assert s.pressure == 0.0
    for _ in range(3):
        s.submit(req("validation", 50.0))
    # one granted immediately (1 validation device in the (1,1) split),
    # two queued
    assert s.pressure == pytest.approx(1.0)


# ------------------------------------------- arrival-rate reallocation
def test_arrival_rate_reallocation_converges_on_bursts():
    """Bursty val-heavy then prof-heavy phases shift the split WITHOUT
    any iteration boundary (continuous reallocation)."""
    loop, s = mk(n=10, realloc="arrival-rate", rate_halflife=100.0)
    t = 0.0
    for i in range(60):                      # validation-heavy phase
        t += 5.0
        loop.schedule(t, lambda: s.submit(req("validation", 1.0)))
        if i % 6 == 0:
            loop.schedule(t, lambda: s.submit(req("profiling", 1.0)))
    loop.run()
    nv_phase1, np_phase1 = s.capacity
    assert nv_phase1 > np_phase1, (s.capacity, s.arrival_rates)
    for i in range(60):                      # profiling-heavy phase
        t += 5.0
        loop.schedule(t - loop.now, lambda: s.submit(req("profiling", 1.0)))
        if i % 6 == 0:
            loop.schedule(t - loop.now,
                          lambda: s.submit(req("validation", 1.0)))
    loop.run()
    nv_phase2, np_phase2 = s.capacity
    assert np_phase2 > nv_phase2, (s.capacity, s.arrival_rates)
    # both pools always keep at least one device (bounded formula)
    assert min(nv_phase1, np_phase1, nv_phase2, np_phase2) >= 1


def test_arrival_rates_decay_to_zero():
    loop, s = mk(n=4, realloc="arrival-rate", rate_halflife=10.0)
    s.submit(req("validation", 1.0))
    rv0, _ = s.arrival_rates
    assert rv0 > 0
    loop.schedule(200.0, lambda: None)       # 20 halflives later
    loop.run()
    rv1, _ = s.arrival_rates
    assert rv1 < rv0 / 1000


# -------------------------------------------------- golden-trace compat
def test_golden_trace_specgen_matches_pr2_records():
    """Deferred execution is trace-invariant: under the PR-2 compat
    plane the refactor reproduces the records captured before it."""
    res, _, _ = run_specgen("T2", model="glm", iterations=12, seed=3,
                            priority=False)
    g = json.loads((GOLDEN / "specgen_T2_glm_it12_seed3.json").read_text())
    assert [dataclasses.asdict(r) for r in res.records] == g["records"]
    assert res.history == g["history"]
    assert res.e2e_time == g["e2e_time"]
    assert res.total_tokens == g["total_tokens"]
    assert res.early_terminations == g["early_terminations"]


def test_golden_trace_shared_pool_matches_pr2_records():
    sched, ctls = run_shared_pool(["T1", "T2", "T3"], model="glm",
                                  iterations=6, devices=4, seed=0,
                                  realloc="queue-max", priority=False)
    g = json.loads((GOLDEN / "pool_T123_glm_it6_d4_seed0.json").read_text())
    for c in ctls:
        r = c.result
        assert [dataclasses.asdict(x) for x in r.records] \
            == g[r.task_id]["records"], r.task_id
        assert r.e2e_time == g[r.task_id]["e2e_time"]
        assert r.total_tokens == g[r.task_id]["total_tokens"]


def test_new_default_plane_is_deterministic():
    """arrival-rate + priority: event-for-event run-to-run identical."""
    a = run_shared_pool(["T1", "T2"], model="glm", iterations=5,
                        devices=4, seed=1)
    b = run_shared_pool(["T1", "T2"], model="glm", iterations=5,
                        devices=4, seed=1)
    for ca, cb in zip(a[1], b[1]):
        assert [dataclasses.asdict(x) for x in ca.result.records] \
            == [dataclasses.asdict(x) for x in cb.result.records]
    assert len(a[0].timeline) == len(b[0].timeline)
    assert a[0].timeline == b[0].timeline


# ------------------------------------------------------- real-eval plane
def test_real_eval_no_build_side_effects_before_grant():
    from repro.search.real_eval import RealEvalBackend
    loop, s = mk(n=2)
    be = RealEvalBackend()
    fut = be.submit_validate(cand("T6", bm=64, bn=64, bk=32))
    assert be.builds_started == 0 and not be._check_cache
    s.submit(req("validation", 30.0))        # occupy the validation device
    s.submit(fut.request)
    assert be.builds_started == 0, "build ran before the device grant"
    loop.run()
    assert be.builds_started == 1
    assert fut.done and fut.value.ok
    assert fut.request.duration > 0.0        # measured wall-clock build


def test_real_eval_batches_coresident_same_builds():
    from repro.search.real_eval import RealEvalBackend
    loop, s = mk(n=2)
    be = RealEvalBackend()
    futs = [be.submit_validate(cand("T6", bm=64, bn=64, bk=32))
            for _ in range(3)]
    assert be.builds_started == 0
    for f in futs:
        s.submit(f.request)
    loop.run()
    assert be.builds_started == 1            # ONE build for the batch
    assert be.batched_hits == 2
    assert all(f.done and f.value.ok for f in futs)
    # different block config => different build
    f2 = be.submit_validate(cand("T6", bm=128, bn=64, bk=32))
    s.submit(f2.request)
    loop.run()
    assert be.builds_started == 2


def test_real_eval_builds_overlap_live_reasoning_4_devices():
    """Acceptance: on a 4-device pool, >= 2 interpret-mode builds are
    granted (and therefore EXECUTE) while the reasoning generation of
    the same iteration is still streaming."""
    from repro.search.real_eval import RealEvalBackend
    loop = EventLoop()
    sched = ElasticScheduler(loop, SchedulerConfig(num_devices=4))
    be = RealEvalBackend()
    ctl = SpecController(
        loop, sched, SimLLMBackend(WorkloadModel("glm", seed=0)), be,
        FeedbackSearch(), SpecGenConfig(iterations=1, termination="none"))
    res = ctl.run_task("T6")
    rec = res.records[0]
    window = (rec.t_start, rec.t_start + rec.gen_time)
    overlapping = [
        r for r in sched.completed
        if r.kind == "validation" and r.candidate.origin == "spec"
        and r.started is not None and window[0] <= r.started < window[1]]
    assert len(overlapping) >= 2, (len(overlapping), window)
    assert be.builds_started >= 2


# ------------------------------------------- predictive fork throttle
def test_predictive_pressure_rises_before_queue_growth():
    """A synthetic co-tenant burst lifts ``pressure`` past the fork
    cutoff (1.0) while the RAW queue signal is still far below it: the
    smoothed arrival rate x mean service time anticipates the backlog
    the burst is about to create."""
    loop, s = mk(n=4, realloc="arrival-rate", rate_halflife=5.0)
    # establish the validation service-time estimate (~50 s)
    for _ in range(2):
        s.submit(req("validation", 50.0))
    loop.run()
    assert s._svc_val == pytest.approx(50.0)
    # burst: rapid-fire arrivals, devices soak most of them up
    for _ in range(4):
        s.submit(req("validation", 50.0))
    raw = len(s.q_val) / s.cfg.num_devices
    assert raw < 1.0                       # queue has NOT filled yet
    assert s.pressure >= 1.0, (s.pressure, s.arrival_rates, s._svc_val)
    # the raw signal is what queue-max mode (and the PR-3 goldens) see
    s.cfg.predictive_pressure = False
    assert s.pressure == pytest.approx(raw)


def test_predictive_pressure_throttles_forks_ahead_of_queues():
    """Regression for the ROADMAP item: under the burst above, a
    controller consulting ``sched.pressure`` stops forking BEFORE the
    validation queue fills; with the predictive term disabled the same
    queue state would still fork."""
    from repro.core.types import IterationRecord

    def forked(predictive: bool) -> int:
        loop, s = mk(n=4, realloc="arrival-rate", rate_halflife=5.0,
                     predictive_pressure=predictive)
        llm = SharedScriptLLM()
        ctl = SpecController(loop, s, llm,
                             SimEvalBackend(WorkloadModel("glm", seed=0)),
                             FeedbackSearch(),
                             SpecGenConfig(iterations=1))
        ctl._task_id, ctl._ctx = "T1", {}
        ctl._tok = {"reason": 0.0, "spec": 0.0, "cached": 0.0}
        handle = types.SimpleNamespace(progress=lambda: 0.5)
        state = {"it": 0, "rec": IterationRecord(index=0, t_start=0.0),
                 "terminated": False, "reason_done": False, "done": False,
                 "spec_live": 0, "spec_handles": [], "handle": handle}
        for _ in range(2):                     # service-time estimate
            s.submit(req("validation", 50.0))
        loop.run()
        for _ in range(4):                     # the co-tenant burst
            s.submit(req("validation", 50.0))
        ctl._fork(state)
        return state["spec_live"]

    assert forked(predictive=True) == 0        # throttled pre-queue
    assert forked(predictive=False) > 0        # reactive signal forks on


# ------------------------------------------ cross-workflow build cache
def test_result_cache_dedups_rebuilds_across_iterations():
    """A config resubmitted AFTER its batch cell closed used to rebuild;
    the bounded result cache replays it, attributed per workflow."""
    from repro.search.real_eval import RealEvalBackend
    loop, s = mk(n=2)
    be = RealEvalBackend()
    f1 = be.submit_validate(cand("T6", bm=64, bn=64, bk=32))
    f1.request.owner = "w0"
    s.submit(f1.request)
    loop.run()
    assert be.builds_started == 1
    # later iteration / other workflow: same build signature
    f2 = be.submit_validate(cand("T6", bm=64, bn=64, bk=32))
    f2.request.owner = "w1"
    s.submit(f2.request)
    loop.run()
    assert be.builds_started == 1              # NO rebuild
    assert be.cache_hits == 1
    assert f2.done and f2.value.ok
    assert be.cache_hit_rate("w1") == 1.0
    assert be.cache_hit_rate("w0") == 0.0
    assert 0.0 < be.cache_hit_rate() < 1.0


def test_result_cache_ttl_expiry_and_lru_bound():
    from repro.search.real_eval import RealEvalBackend
    now = [0.0]
    loop, s = mk(n=2)
    be = RealEvalBackend(result_cache_size=2, result_cache_ttl=10.0,
                         clock=lambda: now[0])

    def run_one(bm):
        f = be.submit_validate(cand("T6", bm=bm, bn=64, bk=32))
        s.submit(f.request)
        loop.run()
        return f

    run_one(64)
    now[0] = 5.0
    run_one(64)
    assert be.builds_started == 1 and be.cache_hits == 1   # within TTL
    now[0] = 20.0                            # 15 s later: entry expired
    run_one(64)
    assert be.builds_started == 2 and be.cache_expired == 1
    # LRU bound: size 2 — building two more signatures evicts bm=64
    run_one(128)
    run_one(32)
    assert be.cache_evictions >= 1
    run_one(64)                              # evicted: rebuilds
    assert be.builds_started == 5


# ----------------------------------------------- controller fork hygiene
class SharedScriptLLM:
    """Backend that hands out ONE shared SpecScript object (a cached/
    deduplicated script, as a real serving backend may)."""

    def __init__(self):
        self.spec = SpecScript(duration=50.0, tokens=10,
                               prompt_tokens=1000, candidate=None)

    def reasoning(self, task_id, it, ctx):
        return ReasoningScript(
            duration=200.0, total_tokens=100,
            chunks=[(20.0, "Let me implement this now. "),
                    (60.0, "Now I will implement the tiled version. ")],
            candidate_fn=lambda: None)

    def speculative(self, task_id, it, ctx, prefix_frac):
        return self.spec


def test_fork_does_not_mutate_backend_owned_spec_script():
    """prefix_cache=False charges the re-prefill latency locally; the
    backend's SpecScript must come back untouched (a shared script
    would otherwise be double-charged on every fork)."""
    loop = EventLoop()
    sched = ElasticScheduler(loop, SchedulerConfig(num_devices=2))
    llm = SharedScriptLLM()
    ctl = SpecController(
        loop, sched, llm, SimEvalBackend(WorkloadModel("glm", seed=0)),
        FeedbackSearch(),
        SpecGenConfig(iterations=1, termination="none", idle_fork=False,
                      prefix_cache=False))
    res = ctl.run_task("T1")
    assert llm.spec.duration == 50.0, "controller mutated the SpecScript"
    assert res.spec_tokens > 0                  # forks did happen + charge


def test_real_eval_compiler_refusal_is_a_build_failure(monkeypatch):
    """A tile the compiler refuses (Mosaic raises while lowering) is the
    paper's build error, counted as a refusal — not a runtime error."""
    from repro.search import real_eval

    def refuse(*_a, **_kw):
        raise ValueError("block shape must be divisible by 8 and 128")

    monkeypatch.setattr(real_eval, "matmul", refuse)
    ev = real_eval.RealEvalBackend()
    _, res = ev.validate(cand("T6", bm=64, bn=64, bk=32))
    assert not res.ok and res.failure == "compile"
    assert (ev.builds_refused, ev.builds_passed) == (1, 0)


def test_real_eval_counts_validated_builds():
    from repro.search.real_eval import RealEvalBackend
    ev = RealEvalBackend()
    _, res = ev.validate(cand("T6", bm=128, bn=128, bk=128))
    assert res.ok and res.speedup_firstcut > 0
    assert (ev.builds_refused, ev.builds_passed) == (0, 1)
