"""The benchmark's one command.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the chip the process finds and
prints, as the last line of standard output, one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics with ``--trace 0``, its per-layer metrics with
``--trace 1``), ``device``, with ``--trace 1`` a ``breakdown``, and last
``checks``: each number compared with its limit.

Everything a cell is made of is found by name: the configuration file
named in ``BENCHMARK.json``, the traffic file ``bench/traffic/<traffic>.json``
whose ``kind`` names the driver ``bench/drivers/<kind>.py``, the
architecture ``bench/arch/<arch>.py`` (shapes, weights, pool, work) and
the reference ``bench/references/<reference>.py`` the configuration
names, the limits ``bench/limits/<cell>.json`` and one reader
``bench/metrics/<metric>.py`` per per-layer metric.

Set-up (``setup_s``) runs from the start of this process to the start of
the measured window: weights made on the device from the seed, the
engine built, every shape of the cell compiled or loaded from the
persistent compilation cache at ``<checkout>/.jax_cache``.  There is no
fallback: without an accelerator, or with fewer chips than the cell
asks for, the run exits nonzero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse                                            # noqa: E402
import importlib                                           # noqa: E402
import importlib.util                                      # noqa: E402
import json                                                # noqa: E402
import os                                                  # noqa: E402
import shutil                                              # noqa: E402
import sys                                                 # noqa: E402
import tempfile                                            # noqa: E402
from pathlib import Path                                   # noqa: E402
from types import SimpleNamespace                          # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
CACHE_DIR = ROOT / ".jax_cache"
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
if str(ROOT / "src") not in sys.path:
    sys.path.insert(1, str(ROOT / "src"))

# the traced part of a --trace 1 window: it starts this far in and lasts
# this long (a share of the window, at most TRACE_MAX_S seconds)
TRACE_FROM, TRACE_SHARE, TRACE_MAX_S = 0.25, 0.5, 10.0

from bench.lib import arch                                 # noqa: E402
from bench.lib.arch import CellError                       # noqa: E402,F401


def load_spec(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def resolve(spec: dict, workload: str, root: Path = ROOT):
    """(cell, configuration entry, configuration file, traffic file)."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise CellError(f"no workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    confs = {c["name"]: c for c in spec["configs"]}
    centry = confs[cell["config"]]
    config = json.loads((root / centry["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{cell['traffic']}.json").read_text())
    return cell, centry, config, traffic


def metrics_for(spec: dict, cell: str, trace: bool) -> list:
    """The metrics a cell reports: end-to-end ones with trace off,
    per-layer ones with it on, each where its ``workloads`` lists the
    cell (or everywhere its end-to-end metric is reported)."""
    e2e = [m for m in spec["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if cell in m.get("workloads", [cell]) if m["moves"] in names]


def load_reader(name: str, root: Path = ROOT):
    path = root / "bench" / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def program_config(config: dict):
    """The program's own configuration of this architecture, checked
    against the configuration file: a width that differs is an error."""
    from repro.models.registry import get_config
    pc = get_config(config["program_arch"])
    check_widths(config, pc)
    return pc


def check_widths(config: dict, pc) -> None:
    want = arch.module(config).widths(config)
    got = {k: getattr(pc, k) for k in want}
    if got != want:
        raise CellError(f"program config {pc.name} differs from the "
                        f"configuration file: {got} != {want}")


class Harness:
    """What a driver gets: the cell's files, the seed, the program's
    configuration and the pieces of set-up that do not depend on the
    traffic kind."""

    def __init__(self, config, traffic, seed, trace, pc, device_kind):
        self.config, self.traffic = config, traffic
        self.seed, self.trace = seed, trace
        self.program_cfg, self.device_kind = pc, device_kind

    def check_params(self, params) -> None:
        """The weights must have the exact tree, shapes and types the
        program's schema declares."""
        import jax
        from repro.models import schema
        want = jax.tree.map(lambda a: (a.shape, a.dtype),
                            schema.abstract_params(self.program_cfg))
        got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
        if jax.tree.structure(want) != jax.tree.structure(got) or \
                jax.tree.leaves(want) != jax.tree.leaves(got):
            raise CellError("generated weights do not match the program's "
                            "parameter schema")

    def make_loop(self, cls):
        """The event loop; in a traced run every event it runs is a host
        span named by the event's tag, so idle gaps can be labelled."""
        if not self.trace:
            return cls()
        from jax.profiler import TraceAnnotation

        class TracedLoop(cls):
            def schedule(self, delay, fn, tag=""):
                def run(fn=fn, name=f"bench.event.{tag or 'untagged'}"):
                    with TraceAnnotation(name):
                        fn()
                return super().schedule(delay, run, tag)
        return TracedLoop()

    def num_pages(self) -> int:
        """Page pool sized from the HBM left after weights, workspace
        (``assumed`` in the configuration file) and the fixed-size state
        of ``max_batch`` slots, not from max_batch x max_len."""
        from bench.lib import work
        c, a = self.config, self.config["assumed"]
        shapes = arch.module(c)
        hbm = work.peaks(self.device_kind)["hbm_bytes"]
        free = (hbm * a["hbm_fill"] - 2 * work.param_count(c)
                - a["workspace_bytes"]
                - self.traffic["max_batch"] * shapes.state_bytes_per_row(c))
        return int(free // shapes.page_bytes(c, a["page_size"]))


class Window:
    """The measured window, polled by the event loop's stop hook.  With
    tracing on, the profiler runs over the middle of it, and the
    driver's counters are read at the traced part's edges."""

    def __init__(self, seconds: float, trace_dir, driver, meter):
        self.seconds, self.trace_dir = seconds, trace_dir
        self.driver, self.meter = driver, meter
        self.t0 = self.t1 = None
        self.slice = None                 # [t_a, t_b, c_a, c_b, k_a, k_b]
        self._ann = None

    def start(self) -> None:
        self.compiles0 = self.meter.snapshot()
        self.c0 = self.driver.counters()
        self.t0 = time.perf_counter()
        a = self.seconds * TRACE_FROM
        self.trace_at = (a, a + min(self.seconds * TRACE_SHARE, TRACE_MAX_S))

    def poll(self) -> bool:
        now = time.perf_counter()
        el = now - self.t0
        if self.trace_dir is not None:
            if self.slice is None and el >= self.trace_at[0]:
                self._trace_on()
            elif self.slice is not None and len(self.slice) == 3 and \
                    el >= self.trace_at[1]:
                self._trace_off()
        if el >= self.seconds:
            self.t1 = now
            self.c1 = self.driver.counters()
            self.compiles1 = self.meter.snapshot()
            return True
        return False

    def _trace_on(self) -> None:
        import jax
        from jax.profiler import TraceAnnotation
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(self.trace_dir),
                                 profiler_options=opts)
        self.driver.record_steps = True
        c = self.driver.counters()
        self._ann = TraceAnnotation("bench.traced")
        self._ann.__enter__()
        self.slice = [time.perf_counter(), c, c["decode_dispatches"]]

    def _trace_off(self) -> None:
        import jax
        self._ann.__exit__(None, None, None)
        c = self.driver.counters()
        self.slice += [time.perf_counter(), c, c["decode_dispatches"]]
        self.driver.record_steps = False
        jax.profiler.stop_trace()


def traced_window(events) -> tuple:
    span = [e for e in events if e[0] == "host" and e[2] == "bench.traced"]
    if not span:
        raise CellError("the trace holds no bench.traced span")
    return span[0][3], span[0][3] + span[0][4]


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             root: Path = ROOT, allow_cpu: bool = False,
             overrides: dict = None, log=print,
             control: bool = False) -> dict:
    """One run of one cell; returns the result object."""
    import jax
    from bench.lib.meter import CompileMeter

    spec = load_spec(root)
    cell, _entry, config, traffic = resolve(spec, workload, root)
    overrides = overrides or {}
    config = overrides.get("config", config)
    traffic = {**traffic, **overrides.get("traffic", {})}
    devs = jax.devices()
    dev = devs[0]
    if not allow_cpu and (dev.platform == "cpu" or
                          len(devs) < cell["chips"]):
        raise CellError(f"needs {cell['chips']} accelerator chip(s); JAX "
                        f"found {len(devs)} {dev.platform} device(s)")
    meter = CompileMeter()
    pc = overrides.get("program_cfg") or program_config(config)
    if "program_cfg" in overrides:
        check_widths(config, pc)
    h = Harness(config, traffic, seed, trace, pc,
                overrides.get("device_kind", dev.device_kind))
    driver = importlib.import_module(
        f"bench.drivers.{traffic['kind']}").Driver(h)
    driver.setup()
    jax.effects_barrier()
    trace_dir = Path(tempfile.mkdtemp(prefix="bench-trace-")) if trace \
        else None
    win = Window(seconds, trace_dir, driver, meter)
    setup_s = time.perf_counter() - T_START
    win.start()
    driver.run_window(win)
    if win.t1 is None:                    # the loop ran dry: a fault
        raise CellError("the traffic stopped before the window closed")
    if trace and win.slice is not None and len(win.slice) == 3:
        win._trace_off()
    compiles = win.compiles1[0] - win.compiles0[0]
    stats = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": jax.device_count(),
              "memory_peak_bytes": stats.get("peak_bytes_in_use")}
    e2e = driver.end_to_end(win.t0, win.t1, win.c0, win.c1)
    e2e["setup_s"] = setup_s
    result_extra = {}
    metrics = {}
    wanted = metrics_for(spec, workload, trace)
    if trace:
        from bench.lib import trace as tr
        xp = sorted(trace_dir.glob("**/*.xplane.pb"))
        events = tr.load(xp[-1])
        shutil.rmtree(trace_dir, ignore_errors=True)
        red = tr.reduce(events, traced_window(events))
        t_a, c_a, k_a, t_b, c_b, k_b = win.slice
        ctx = SimpleNamespace(
            config=config, device_kind=h.device_kind, trace=red,
            c0=c_a, c1=c_b, host_s=t_b - t_a,
            steps=driver.step_contexts(k_a, k_b))
        for m in wanted:
            v = load_reader(m["name"], root)(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        result_extra["breakdown"] = {"device_ops": red["device_ops"],
                                     "idle_gaps": red["idle_gaps"]}
    else:
        for m in wanted:
            if m["name"] in e2e:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
    log(f"[window] seconds={win.t1 - win.t0:.3f} compiles_in_window="
        f"{compiles} counters_start={json.dumps(win.c0)} "
        f"counters_end={json.dumps(win.c1)} itl_samples="
        f"{e2e.get('_itl_samples')}", file=sys.stderr, flush=True)
    driver.release()
    reference = importlib.import_module(
        f"bench.references.{config['reference']}")
    t_check = time.perf_counter()
    found = driver.check(reference, control=control)
    log(f"[check] seconds={time.perf_counter() - t_check:.3f}",
        file=sys.stderr, flush=True)
    limits = overrides.get("limits") or json.loads(
        (root / "bench" / "limits" / f"{workload}.json").read_text())
    checks = {name: {"value": found[name], "limit": lim["limit"]}
              for name, lim in limits["checks"].items()}
    correct = all(c["value"] <= c["limit"] for c in checks.values()) and \
        found.get("tokens_checked", 1) > 0
    for k, v in found.items():
        if k not in checks:
            log(f"[check] {k}={v!r}", file=sys.stderr, flush=True)
    for name, c in checks.items():
        log(f"[check] {name}={c['value']!r} limit={c['limit']!r}",
            file=sys.stderr, flush=True)
    detail = {k: v for k, v in found.items() if k not in checks}
    return {"correct": bool(correct), "attempted": driver.attempted,
            "failed": 0, "metrics": metrics, "device": device,
            **result_extra, "compiles_in_window": compiles,
            "check_detail": detail, "checks": checks}


def use_compile_cache() -> None:
    """Every executable goes to the persistent cache in the checkout, so
    that only a cell's first run there compiles.  The cache lives in
    the checkout, whatever the environment names."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    use_compile_cache()
    try:
        res = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace))
    except CellError as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 2
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
