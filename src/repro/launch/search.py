"""SpecGen end-to-end driver CLI.

    PYTHONPATH=src python -m repro.launch.search --task T6 \
        --model glm --iterations 40 --algorithm refine \
        --termination hist-avg [--real-eval] [--devices 2]

--real-eval validates candidates by BUILDING the Pallas matmul template
(compiled on a TPU, interpreted on the CPU) and prices them with the TPU
cost model; otherwise the calibrated simulation backend is used
(deterministic, fast).
"""
from __future__ import annotations

import argparse

from repro.core.clock import EventLoop
from repro.core.controller import SpecController, SpecGenConfig
from repro.core.scheduler import ElasticScheduler, SchedulerConfig
from repro.core.termination import CRITERIA
from repro.launch.compile_cache import enable_compile_cache
from repro.search.algorithms import ALGORITHMS
from repro.search.llm_sim import SimEvalBackend, SimLLMBackend
from repro.search.workload import WorkloadModel


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--task", default="T4")
    ap.add_argument("--model", default="glm", choices=["glm", "dsv4"])
    ap.add_argument("--iterations", type=int, default=40)
    ap.add_argument("--devices", type=int, default=2)
    ap.add_argument("--algorithm", default="refine",
                    choices=list(ALGORITHMS))
    ap.add_argument("--termination", default="hist-avg",
                    choices=list(CRITERIA))
    ap.add_argument("--no-speculation", action="store_true")
    ap.add_argument("--no-prefix-cache", action="store_true")
    ap.add_argument("--scheduler", default="elastic",
                    choices=["elastic", "static"])
    ap.add_argument("--realloc", default="queue-max",
                    choices=["queue-max", "arrival-rate"],
                    help="pool reallocation: Algorithm-2 iteration-"
                         "boundary queue maxima, or continuous EWMA "
                         "arrival rates")
    ap.add_argument("--no-priority", action="store_true",
                    help="disable fallback-over-speculative ordering "
                         "(PR-2 legacy LAF/FIFO queues)")
    ap.add_argument("--real-eval", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    loop = EventLoop()
    wl = WorkloadModel(model=args.model, seed=args.seed)
    sched = ElasticScheduler(loop, SchedulerConfig(
        num_devices=args.devices, mode=args.scheduler,
        realloc=args.realloc, priority=not args.no_priority))
    if args.real_eval:
        from repro.search.real_eval import RealEvalBackend
        evaluator = RealEvalBackend()
    else:
        evaluator = SimEvalBackend(wl)
    ctl = SpecController(
        loop, sched, SimLLMBackend(wl), evaluator,
        ALGORITHMS[args.algorithm](),
        SpecGenConfig(iterations=args.iterations,
                      termination=args.termination,
                      enable_speculation=not args.no_speculation,
                      prefix_cache=not args.no_prefix_cache))
    res = ctl.run_task(args.task)

    print(f"task={res.task_id} algo={args.algorithm} "
          f"term={args.termination}")
    print(f"  e2e={res.e2e_time/1e3:.1f}ks  feedback="
          f"{res.profiling_feedback}  early_term="
          f"{res.early_terminations}/{args.iterations}")
    print(f"  best_speedup={res.best_speedup:.2f}x  tokens="
          f"{res.total_tokens/1e6:.2f}M (cached prefix: "
          f"{res.cached_prefix_tokens/1e6:.2f}M)")
    print(f"  pool busy-fraction={sched.utilization_any():.1%} "
          f"device-seconds={sched.utilization():.1%}")
    if args.real_eval:
        print(f"  real-eval (deferred): builds={evaluator.builds_started} "
              f"batched={evaluator.batched_hits} "
              f"submits={evaluator.submits}")


if __name__ == "__main__":
    main()
