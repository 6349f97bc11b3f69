"""End-to-end agentic kernel optimization with REAL kernel evaluation.

Every candidate is a real config of the Pallas tiled-matmul template:
validation BUILDS the kernel (Mosaic on a TPU, the interpreter on the
CPU) and checks it against the jnp oracle; profiling prices it with the
TPU roofline cost model.
The search therefore optimizes a genuine kernel: watch the best block
configuration improve over iterations.

By default the LLM side ALSO runs for real (DESIGN.md §One-loop): the
workflow's reasoning is continuous-batched decode on a loop-clocked
``serving.Engine`` — speculative forks are ``Engine.fork()`` zero-copy
page shares, and early termination cancels the live decode row
mid-stream (the remaining tokens are never dispatched).  Pass ``sim``
as the third argument to replay the scripted generation path instead.

Evaluation is DEFERRED (DESIGN.md §Async-eval-plane): submission only
queues a thunk, the kernel build runs when the elastic pool
grants a device — overlapping the still-streaming reasoning trace —
and same-build requests co-resident in the queue share one build;
repeated configs across iterations replay from the bounded build-result
cache.  The remote-KV transport plane (DESIGN.md §Remote-KV-transport)
rides the same loop: every speculative fork fetches its reasoning
prefix over the modeled link, and the fetch latency lands in the fork's
availability time.

    PYTHONPATH=src python examples/kernel_search.py [task] [iters] [llm]
"""
import sys

from repro.search.driver import run_specgen
from repro.search.real_eval import RealEvalBackend
from repro.kernels.matmul.ops import estimate_cost, reference_cost
from repro.search.tasks import TASKS

task = sys.argv[1] if len(sys.argv) > 1 else "T6"
iters = int(sys.argv[2]) if len(sys.argv) > 2 else 12
llm = sys.argv[3] if len(sys.argv) > 3 else "engine"

evaluator = RealEvalBackend()
res, sched, ctl = run_specgen(
    task, iterations=iters, devices=4, realloc="arrival-rate",
    evaluator=evaluator, transport="async", llm=llm, trace=True)
transport = ctl.transport

# deferred-plane accounting: speculative validations GRANTED a device
# (thunk executed: a build, or a batched replay of one) while the
# iteration's reasoning generation was still streaming
overlapped = 0
for rec in res.records:
    if not rec.gen_time:
        continue
    lo, hi = rec.t_start, rec.t_start + rec.gen_time
    overlapped += sum(
        1 for r in sched.completed
        if r.kind == "validation" and r.candidate.origin == "spec"
        and r.started is not None and lo <= r.started < hi)

td = TASKS[task]
print(f"\ntask {task} ({td.name}), {iters} iterations, "
      f"{res.profiling_feedback} profiled kernels, llm={llm}")
best = res.best_candidate
if best is not None:
    cfg = {k: v for k, v in best.config.items()
           if not k.startswith("_")}
    cost = estimate_cost(td.M, td.N, td.K, bm=cfg["bm"], bn=cfg["bn"],
                         bk=cfg["bk"], mask=td.mask)
    ref = reference_cost(td.M, td.N, td.K, mask=td.mask)
    print(f"best config: {cfg}  (origin={best.origin}, "
          f"prefix={best.prefix_frac:.0%})")
    print(f"cost-model speedup over reference: "
          f"{ref.runtime_s/cost.runtime_s:.2f}x "
          f"(VMEM {cost.vmem_bytes/2**20:.1f} MiB, "
          f"aligned={cost.mxu_aligned})")
print(f"history: {[round(h, 2) for h in res.history[1:]]}")
print(f"deferred eval plane: {evaluator.builds_started} builds "
      f"({evaluator.batched_hits} batched, {evaluator.cache_hits} "
      f"cache hits, {evaluator.cache_hit_rate():.0%} rate) of "
      f"{evaluator.submits} submits; {overlapped} spec evals granted "
      f"during live reasoning")

# transport-plane accounting: fork-prefix fetches that rode the modeled
# RDMA link, and how many started while reasoning was still streaming
fetch_overlap = 0
for rec in res.records:
    if not rec.gen_time:
        continue
    lo, hi = rec.t_start, rec.t_start + rec.gen_time
    fetch_overlap += sum(
        1 for (t, plane, ev, tag) in transport.loop.trace
        if plane == "transport" and ev == "start"
        and tag.split(":")[1].startswith("prefix") and lo <= t < hi)
mean_fetch = res.prefix_fetch_s / max(res.prefix_fetches, 1)
print(f"remote-KV transport: {res.prefix_fetches} prefix fetches "
      f"({transport.link.bytes_moved / 2**20:.1f} MiB moved, mean "
      f"{mean_fetch * 1e3:.2f} ms/fetch), {fetch_overlap} overlapped "
      f"live reasoning; link util {sched.transport_utilization():.1%}")

# engine-backed serving substrate: the same numbers the paper's
# speculative-generation story is about, read off the REAL engine
if llm == "engine":
    gen, eng = ctl.gen, ctl.gen.engine
    print(f"engine substrate: {gen.forks} Engine.fork() forks "
          f"({gen.forks_denied} declined), "
          f"{eng.store.stats.pages_shared} KV pages shared zero-copy; "
          f"{eng.tokens_decoded} tokens decoded, "
          f"{gen.tokens_not_decoded} cancelled before dispatch "
          f"({res.early_terminations} early terminations)")
