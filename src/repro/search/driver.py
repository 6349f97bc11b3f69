"""One-call drivers assembling the full stacks (benchmarks/examples)."""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

from repro.core.clock import EventLoop
from repro.core.controller import SpecController, SpecGenConfig, TaskResult
from repro.core.scheduler import ElasticScheduler, SchedulerConfig
from repro.serving.transport import TransportConfig, TransportPlane
from repro.search.baselines import (BASELINES, BaselineHarness,
                                    one_gpu_per_kernel_scheduler)
from repro.search.llm_sim import FeedbackSearch, SimEvalBackend, SimLLMBackend
from repro.search.workload import WorkloadModel


def _make_transport(loop: EventLoop, sched: ElasticScheduler,
                    transport, decode_step_s: Optional[float] = None
                    ) -> Optional[TransportPlane]:
    """``transport``: None (legacy, no modeled remote-KV link) or
    "async"/"sync" (build a plane on the pool's loop and attach it).
    ``decode_step_s`` overrides the plane's decode-step grid (the
    engine-backed path uses a calibrated virtual step so real token
    counts span sim-comparable durations)."""
    if transport is None:
        return None
    cfg = TransportConfig(mode=transport) if decode_step_s is None \
        else TransportConfig(mode=transport, decode_step_s=decode_step_s)
    plane = TransportPlane(loop=loop, cfg=cfg)
    sched.attach_transport(plane)
    return plane


# Engine-backed generation (DESIGN.md §One-loop): defaults calibrated
# so ~reasoning_tokens real decode steps x decode_step_s lands near the
# sim's ~700 s mean reasoning duration — speculative forks then have
# time to validate/profile BEFORE reasoning ends, so early termination
# cancels REAL in-flight decode (tokens_not_decoded > 0).  ``size``
# picks the model's widths (``registry.SIZES``): the smoke toy, or the
# published config with random weights drawn from the run's seed.
ENGINE_DEFAULTS = dict(arch="qwen2-1.5b", size="smoke", prompt_len=12,
                       reasoning_tokens=40, spec_tokens=10,
                       decode_step_s=15.0)


def _make_engine(plane: TransportPlane, max_batch: int, opts: dict):
    """One shared Engine on the run's loop (via its transport plane),
    loop-clocked: its decode pump schedules EngineStepEvents on the
    SAME composed timeline as scheduler/transport/eval."""
    import jax as _jax
    from repro.models import schema
    from repro.models.layers import Runtime
    from repro.models.registry import get_sized
    from repro.serving.engine import Engine

    cfg = get_sized(opts["arch"], opts["size"])
    params = schema.init_params(cfg, _jax.random.PRNGKey(opts["seed"]))
    max_len = opts.get("max_len") or (opts["prompt_len"]
                                      + opts["reasoning_tokens"]
                                      + opts["spec_tokens"] + 4)
    return Engine(cfg, params, Runtime(), max_len=max_len,
                  max_batch=max_batch, transport=plane, clocking="event")


def _engine_opts(engine_opts, seed: int) -> dict:
    o = dict(ENGINE_DEFAULTS, seed=seed)
    o.update(engine_opts or {})
    return o


def _make_loop(trace: bool, evaluator, spans: bool = False,
               metrics: bool = False) -> EventLoop:
    """One composed clock per run (DESIGN.md §Engine-on-loop): the
    loop every plane shares.  ``trace=True`` turns on the unified
    (t, plane, event, tag) timeline; ``spans``/``metrics`` switch on
    the causal span tree and the metrics registry (DESIGN.md
    §Observability) — pure bookkeeping, no loop events; an evaluator
    that knows how joins the timeline (RealEvalBackend.attach_loop)."""
    loop = EventLoop()
    if trace:
        loop.enable_trace()
    if spans:
        loop.enable_spans()
    if metrics:
        loop.enable_metrics()
    attach = getattr(evaluator, "attach_loop", None)
    if attach is not None:
        attach(loop)
    return loop


def run_specgen(task_id: str, model: str = "glm", iterations: int = 100,
                devices: int = 2, termination="hist-avg",
                enable_speculation: bool = True, prefix_cache: bool = True,
                scheduler_mode: str = "elastic",
                validation_policy: str = "laf",
                profiling_policy: str = "fifo",
                realloc: str = "queue-max", priority: bool = True,
                seed: int = 0, max_concurrent_spec: int = 8,
                evaluator=None, transport=None, trace: bool = False,
                llm: str = "sim", engine_opts=None,
                spans: bool = False, metrics: bool = False,
                ) -> Tuple[TaskResult, ElasticScheduler, SpecController]:
    """``llm="sim"`` replays the calibrated scripted path (byte-pinned
    by the goldens); ``llm="engine"`` runs the workflow's generations
    as REAL continuous-batched decode on a loop-clocked Engine
    (forks = Engine.fork, early termination cancels live rows)."""
    assert llm in ("sim", "engine")
    if llm == "engine" and transport is None:
        transport = "async"                  # the engine needs the plane
    eo = _engine_opts(engine_opts, seed)
    loop = _make_loop(trace, evaluator, spans=spans, metrics=metrics)
    wl = WorkloadModel(model=model, seed=seed)
    sched = ElasticScheduler(loop, SchedulerConfig(
        num_devices=devices, mode=scheduler_mode,
        validation_policy=validation_policy,
        profiling_policy=profiling_policy,
        realloc=realloc, priority=priority,
        static_split=((devices - devices // 2, devices // 2)
                      if scheduler_mode == "static" else None)))
    plane = _make_transport(
        loop, sched, transport,
        decode_step_s=eo["decode_step_s"] if llm == "engine" else None)
    if llm == "engine":
        from repro.search.llm_engine import EngineGeneration
        engine = _make_engine(plane, 1 + max_concurrent_spec, eo)
        gen = EngineGeneration(
            engine, SimLLMBackend(wl), name="w0",
            prompt_len=eo["prompt_len"],
            reasoning_tokens=eo["reasoning_tokens"],
            spec_tokens=eo["spec_tokens"], seed=seed)
    else:
        gen = SimLLMBackend(wl)
    ctl = SpecController(
        loop, sched, gen,
        SimEvalBackend(wl) if evaluator is None else evaluator,
        FeedbackSearch(),
        SpecGenConfig(iterations=iterations, termination=termination,
                      enable_speculation=enable_speculation,
                      prefix_cache=prefix_cache,
                      max_concurrent_spec=max_concurrent_spec),
        transport=plane)
    res = ctl.run_task(task_id)
    return res, sched, ctl


def run_baseline(name: str, task_id: str, model: str = "glm",
                 iterations: int = 100, seed: int = 0,
                 token_budget: Optional[float] = None,
                 ) -> Tuple[TaskResult, ElasticScheduler]:
    loop = EventLoop()
    wl = WorkloadModel(model=model, seed=seed)
    sched = one_gpu_per_kernel_scheduler(loop)
    h = BaselineHarness(loop, sched, SimLLMBackend(wl), SimEvalBackend(wl),
                        BASELINES[name], iterations=iterations,
                        token_budget=token_budget)
    res = h.run_task(task_id)
    return res, sched


def run_shared_pool(tasks, model: str = "glm", iterations: int = 100,
                    devices: int = 10, seed: int = 0,
                    scheduler_mode: str = "elastic",
                    validation_policy: str = "laf",
                    profiling_policy: str = "fifo",
                    realloc: str = "arrival-rate", priority: bool = True,
                    work_stealing: bool = False,
                    enable_speculation: bool = True,
                    prefix_cache: bool = True,
                    termination="hist-avg", evaluator=None,
                    transport=None, trace: bool = False,
                    llm: str = "sim", engine_opts=None,
                    spans: bool = False, metrics: bool = False):
    """The paper's evaluation setting: N workflows sharing one pool.

    The pool runs the async evaluation plane by default: continuous
    arrival-rate reallocation (the bursty multi-workflow setting it was
    built for) and fallback-over-speculative priority.  ``realloc=
    "queue-max", priority=False`` restores the PR-2 legacy plane
    (benchmarks/table_async_overlap.py measures the difference).
    ``trace=True`` records the composed (t, plane, event, tag) timeline
    on the shared loop (``sched.loop.trace``) — gen, eval and transport
    planes on one clock, the trace ``core.trace`` derives makespan and
    per-plane breakdowns from.

    ``llm="engine"`` backs EVERY workflow's generations with ONE
    loop-clocked Engine (the paper's serving substrate): N reasoning
    rows continuous-batch together, forks are Engine.fork() page
    sharing, and early termination cancels real decode.  The shared
    engine is returned as ``sched.engine`` for inspection.
    """
    assert llm in ("sim", "engine")
    if llm == "engine" and transport is None:
        transport = "async"                  # the engine needs the plane
    eo = _engine_opts(engine_opts, seed)
    loop = _make_loop(trace, evaluator, spans=spans, metrics=metrics)
    wl = WorkloadModel(model=model, seed=seed)
    sched = ElasticScheduler(loop, SchedulerConfig(
        num_devices=devices, mode=scheduler_mode,
        validation_policy=validation_policy,
        profiling_policy=profiling_policy,
        realloc=realloc, priority=priority,
        work_stealing=work_stealing,
        static_split=((devices - devices // 2, devices // 2)
                      if scheduler_mode == "static" else None)))
    plane = _make_transport(
        loop, sched, transport,
        decode_step_s=eo["decode_step_s"] if llm == "engine" else None)
    engine = None
    if llm == "engine":
        spec_cap = SpecGenConfig().max_concurrent_spec
        engine = _make_engine(plane, len(tasks) * (1 + spec_cap), eo)
    sched.engine = engine
    sched.transport = plane
    ctls = []
    for i, task in enumerate(tasks):
        if engine is not None:
            from repro.search.llm_engine import EngineGeneration
            gen = EngineGeneration(
                engine, SimLLMBackend(wl), name=f"w{i}",
                prompt_len=eo["prompt_len"],
                reasoning_tokens=eo["reasoning_tokens"],
                spec_tokens=eo["spec_tokens"], seed=seed + i)
        else:
            gen = SimLLMBackend(wl)
        c = SpecController(
            loop, sched, gen,
            SimEvalBackend(wl) if evaluator is None else evaluator,
            FeedbackSearch(),
            SpecGenConfig(iterations=iterations, termination=termination,
                          enable_speculation=enable_speculation,
                          prefix_cache=prefix_cache),
            name=f"w{i}", transport=plane)
        c.start(task)
        ctls.append(c)
    loop.run(stop=lambda: all(c.done for c in ctls))
    return sched, ctls


def run_traffic(arrivals, model: str = "glm", iterations: int = 2,
                devices: int = 10, seed: int = 0,
                tenants=None, admission=None,
                evaluator=None, transport=None, trace: bool = False,
                llm: str = "sim", engine_opts=None,
                spans: bool = False, metrics: bool = True):
    """Open-loop traffic (DESIGN.md §Traffic-plane): a pre-generated
    arrival trace (``core.arrivals``) drives workflow starts as events
    on the one shared loop; every arrival passes the admission
    controller (admit / defer / shed from predicted pressure) and each
    ADMITTED workflow becomes a SpecController on the shared pool with
    its tenant tag and SLO deadline stamped on every eval request —
    the scheduler's SLO heap layer (class rank, weighted per-tenant
    fairness, EDF) orders the queues.

    ``llm="engine"`` backs every admitted workflow with ONE shared
    loop-clocked Engine; ``AdmissionConfig.max_live`` then bounds the
    concurrent workflows so the engine's slot/page budget is sized
    up-front (the page-headroom gate defers the rest).

    Returns ``(sched, adm, flows)``: the scheduler (``sched.engine``
    attached on engine runs), the AdmissionController (decision
    counters, shed bookkeeping) and one completion record per FINISHED
    workflow — ``{"name", "tenant", "slo", "t_arrive", "t_done",
    "latency", "deadline_s", "met"}`` in completion order.  SLO
    attainment is judged from ARRIVAL (deferral time counts against
    the deadline), which is what makes goodput an admission-policy
    metric and not just a scheduler one.
    """
    from repro.core.arrivals import DEFAULT_TENANTS, schedule_arrivals
    from repro.core.scheduler import (AdmissionConfig, AdmissionController,
                                      SLOPolicy)

    assert llm in ("sim", "engine")
    if llm == "engine" and transport is None:
        transport = "async"                  # the engine needs the plane
    eo = _engine_opts(engine_opts, seed)
    arrivals = list(arrivals)
    tenants = tuple(tenants if tenants is not None else DEFAULT_TENANTS)
    pol = SLOPolicy.from_tenants(tenants)
    loop = _make_loop(trace, evaluator, spans=spans, metrics=metrics)
    wl = WorkloadModel(model=model, seed=seed)
    sched = ElasticScheduler(loop, SchedulerConfig(
        num_devices=devices, realloc="arrival-rate", priority=True,
        slo=pol))
    plane = _make_transport(
        loop, sched, transport,
        decode_step_s=eo["decode_step_s"] if llm == "engine" else None)
    adm_cfg = admission if admission is not None else AdmissionConfig()
    engine = None
    if llm == "engine":
        spec_cap = SpecGenConfig().max_concurrent_spec
        if adm_cfg.max_live <= 0:
            adm_cfg = dataclasses.replace(adm_cfg, max_live=4)
        engine = _make_engine(plane, adm_cfg.max_live * (1 + spec_cap), eo)
    sched.engine = engine
    sched.transport = plane
    flows: List[dict] = []
    adm = AdmissionController(loop, sched, adm_cfg, engine=engine)

    def start_workflow(arr) -> None:
        klass = pol.classes.get(arr.slo, pol.classes[pol.default])
        if engine is not None:
            from repro.search.llm_engine import EngineGeneration
            gen = EngineGeneration(
                engine, SimLLMBackend(wl), name=arr.name,
                prompt_len=eo["prompt_len"],
                reasoning_tokens=eo["reasoning_tokens"],
                spec_tokens=eo["spec_tokens"], seed=seed + arr.wid)
        else:
            gen = SimLLMBackend(wl)
        c = SpecController(
            loop, sched, gen,
            SimEvalBackend(wl) if evaluator is None else evaluator,
            FeedbackSearch(),
            SpecGenConfig(iterations=iterations),
            name=arr.name, transport=plane,
            tenant=arr.tenant, deadline_s=klass.deadline_s)

        def finished(ctl, a=arr, k=klass):
            lat = loop.now - a.t           # arrival-anchored: deferral
            flows.append({                 # time counts against the SLO
                "name": a.name, "tenant": a.tenant, "slo": k.name,
                "t_arrive": a.t, "t_done": loop.now, "latency": lat,
                "deadline_s": k.deadline_s, "met": lat <= k.deadline_s})
            adm.workflow_done(lat)
        c.start(arr.task_id, on_done=finished)

    adm.start_fn = start_workflow
    schedule_arrivals(loop, arrivals, adm.offer)
    total = len(arrivals)
    loop.run(stop=lambda: (
        adm.decisions["admit"] + adm.decisions["shed"] >= total
        and len(flows) >= adm.decisions["admit"]))
    return sched, adm, flows


def run_engine_pool(arch: str = "qwen2-1.5b", size: str = "smoke",
                    n_workflows: int = 10,
                    prompt_len: int = 16, reasoning_tokens: int = 24,
                    forks_per_workflow: int = 1, fork_tokens: int = 6,
                    max_len: int = 160, seed: int = 0,
                    trace: bool = False,
                    spans: bool = False, metrics: bool = False,
                    ) -> Tuple["object", Dict[int, List[int]]]:
    """The paper's serving-side setting on the REAL model: N concurrent
    kernel-refinement workflows (one reasoning generation each, plus
    speculative forks mid-stream) share ONE continuous-batched engine.
    Every step is a single jitted dispatch over all live rows with
    on-device sampling; forks share their parent's KV pages via
    block-table copy (zero KV copies, zero prefill recompute) and
    pages copy-on-write lazily as children diverge.

    Since the one-loop refactor (DESIGN.md §One-loop) this runs on the
    SAME stack as the controller drivers — a shared EventLoop with a
    transport plane, the engine loop-clocked (``clocking="event"``) —
    instead of a standalone plane: the mid-stream forks are scheduled
    loop events landing between decode-step events on one composed
    timeline, not manual ``step_all`` pumping.

    Returns (engine, {gen_id: emitted tokens}).
    """
    import numpy as np
    import jax as _jax
    from repro.models import schema
    from repro.models.layers import Runtime
    from repro.models.registry import get_sized
    from repro.serving.engine import Engine

    cfg = get_sized(arch, size)
    params = schema.init_params(cfg, _jax.random.PRNGKey(seed))
    loop = EventLoop()
    if trace:
        loop.enable_trace()
    if spans:
        loop.enable_spans()
    if metrics:
        loop.enable_metrics()
    plane = TransportPlane(loop=loop, cfg=TransportConfig(mode="async"))
    eng = Engine(cfg, params, Runtime(), max_len=max_len,
                 max_batch=n_workflows * (1 + forks_per_workflow),
                 transport=plane, clocking="event")
    rs = np.random.RandomState(seed)
    roots = [eng.submit(list(rs.randint(0, cfg.vocab_size, prompt_len)),
                        max_new_tokens=reasoning_tokens, temperature=0.7,
                        reasoning=True, seed=seed + i)
             for i in range(n_workflows)]
    fork_at = max(2, reasoning_tokens // 3)

    def do_forks():                         # mid-reasoning speculation
        for i, r in enumerate(roots):
            if eng.generation(r).status != "running":
                continue                    # already retired: no parent
            for j in range(forks_per_workflow):
                eng.fork(r, max_new_tokens=fork_tokens, temperature=0.9,
                         seed=seed + 100 * i + j)
    loop.schedule(fork_at * plane.cfg.decode_step_s, do_forks,
                  tag="fork")
    return eng, eng.run_all()
