"""The harness and the traffic drivers at smoke size on the CPU: seeded
schedules, the refusal of the CPU by the measurement path, the
data-driven lookup of cells and metrics, and faults planted under the
timed path that the comparison with the reference must catch."""
import contextlib
import json
import shutil
import sys
from pathlib import Path

import numpy as np
import pytest

from bench_smoke import ROOT, overrides

from bench import run  # noqa: E402

CELL = "qwen2-1.5b.reason-fork-w8"


def drive(seed, n_events=400):
    """A smoke-size driver after its set-up and its first loop events."""
    _cell, _c, _config, traffic = run.resolve(run.load_spec(), CELL)
    o = overrides()
    traffic = {**traffic, **o["traffic"]}
    h = run.Harness(o["config"], traffic, seed, False, o["program_cfg"],
                    o["device_kind"])
    import importlib
    drv = importlib.import_module(
        f"bench.drivers.{traffic['kind']}").Driver(h)
    drv.setup()

    class Events:
        n = 0

        def poll(self):
            self.n += 1
            return self.n > n_events
    drv.run_window(Events())
    return drv


def schedule(drv):
    """The rows a driver created: (id, kind, greedy, parent, prompt
    length, tokens to decode, first tokens)."""
    return [(gid, r["kind"], r["greedy"], r["parent"],
             drv.eng.generation(gid).prompt_len,
             drv.eng.generation(gid).max_new_tokens,
             tuple(drv.eng.generation(gid).tokens[:4]))
            for gid, r in sorted(drv.rows.items())]


def test_same_seed_same_traffic_other_seed_same_work_other_data():
    a = schedule(drive(2 ** 33 + 7))
    assert len(a) > 20
    assert sum(r[1] == "fork" for r in a) > 5      # forks happened
    assert a == schedule(drive(2 ** 33 + 7))
    b = schedule(drive(2 ** 33 + 8))
    assert [r[:6] for r in a] == [r[:6] for r in b]  # the same work
    assert [r[6] for r in a] != [r[6] for r in b]    # other tokens


def test_no_greedy_row_has_a_greedy_parent_or_sibling():
    drv = drive(11)
    rows = drv.rows
    greedy_forks = [g for g, r in rows.items()
                    if r["kind"] == "fork" and r["greedy"]]
    assert greedy_forks and any(r["greedy"] for r in rows.values()
                                if r["kind"] == "reasoning")
    for g in greedy_forks:
        parent = rows[g]["parent"]
        assert not rows[parent]["greedy"]
        # siblings: forks of the same parent made at the same context
        start = drv.eng.generation(g).prompt_len
        assert not any(
            r["greedy"] for f, r in rows.items() if f != g
            and r["parent"] == parent
            and drv.eng.generation(f).prompt_len == start)


def test_window_opens_on_the_simulated_steady_state():
    """The opening state comes from the loop's length process run on
    the host: the workflows are in different iterations, with contexts
    past the widest prompt bucket (admitted through prefixes) and live
    forks; with no request failing on the way."""
    drv = drive(3, n_events=0)
    flows = drv.flows
    assert len({wf.iteration for wf in flows}) > 1
    assert min(wf.iteration for wf in flows) >= 3
    ctx = [drv.eng.generation(wf.reason).prompt_len for wf in flows]
    assert max(ctx) > 128
    assert all(drv.eng.generation(wf.reason).status == "running"
               for wf in flows)
    assert sum(len(wf.forks) for wf in flows) > 0
    assert all(len(wf.forks) <= drv.tr["max_live_forks"] for wf in flows)


def test_measurement_path_refuses_the_cpu():
    with pytest.raises(run.CellError, match="accelerator"):
        run.run_cell(CELL, 1, 1.0, False)


def test_cpu_run_is_correct():
    res = run.run_cell(CELL, 5, 3.0, False, allow_cpu=True,
                       overrides=overrides(), log=lambda *a, **k: None)
    assert res["correct"], res["checks"]
    assert res["compiles_in_window"] == 0
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"decode_tok_s", "itl_p95_ms", "setup_s"}


def _plant(monkeypatch, fault):
    from repro.serving.engine import Engine
    from repro.serving.pagepool import PagePool
    if fault == "token":
        orig = Engine._dispatch_compute

        def altered(self, gens):        # a served token altered
            nxt = orig(self, gens)
            return (np.asarray(nxt) + 1) % self.cfg.vocab_size
        monkeypatch.setattr(Engine, "_dispatch_compute", altered)
    elif fault == "cow":
        def no_copy(self, cache, srcs, dsts):   # CoW copies nothing
            self.page_copies += len(srcs)
            return cache
        monkeypatch.setattr(PagePool, "copy_pages", no_copy)


@pytest.mark.parametrize("fault", ["token", "cow"])
def test_planted_fault_is_not_correct(monkeypatch, fault):
    _plant(monkeypatch, fault)
    res = run.run_cell(CELL, 5, 3.0, False, allow_cpu=True,
                       overrides=overrides(), log=lambda *a, **k: None)
    assert not res["correct"], res["checks"]


def test_a_metric_is_added_by_files_alone(tmp_path):
    """A throwaway per-layer metric: one new reader file and one new
    entry in BENCHMARK.json, no edit to any file the harness has."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["per_layer"].append({
        "name": "throwaway.rows", "unit": "rows", "better": "higher",
        "source": "program_counter", "layer": "engine admission and "
        "batching", "moves": "decode_tok_s"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    (tmp_path / "bench/metrics/throwaway.rows.py").write_text(
        "def read(ctx):\n    return ctx.c1['x'] - ctx.c0['x']\n")
    got = run.metrics_for(run.load_spec(tmp_path), CELL, True)
    assert "throwaway.rows" in [m["name"] for m in got]
    read = run.load_reader("throwaway.rows", tmp_path)

    class Ctx:
        c0, c1 = {"x": 1}, {"x": 4}
    assert read(Ctx) == 3


@contextlib.contextmanager
def harness_at(root):
    """The harness's modules imported from the copy at ``root``, for the
    length of the block; the repository's own are put back after."""
    def ours(name):
        return name == "bench" or name.startswith("bench.")
    saved = {k: sys.modules.pop(k) for k in list(sys.modules) if ours(k)}
    sys.path.insert(0, str(root))
    try:
        yield
    finally:
        sys.path.remove(str(root))
        for k in [k for k in sys.modules if ours(k)]:
            del sys.modules[k]
        sys.modules.update(saved)


# the program's phi3.5-moe smoke preset, as a configuration file
MOE = {"hidden_size": 64, "num_attention_heads": 4,
       "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 128,
       "num_local_experts": 4, "num_experts_per_tok": 2,
       "num_hidden_layers": 2, "vocab_size": 256,
       "tie_word_embeddings": False, "program_arch": "phi3.5-moe-42b-a6.6b",
       "arch": "sparse_moe", "reference": "dense_gqa",
       "assumed": {"page_size": 16, "hbm_fill": 0.001,
                   "workspace_bytes": 0}}


def test_a_configuration_of_another_architecture_is_added_by_files_alone(
        tmp_path):
    """A configuration whose layers route over sparse experts: a new arch
    module, a new configuration file and new entries in BENCHMARK.json,
    no edit to any file the harness has.  The driver's set-up builds its
    engine on weights that pass the program's schema, with the pool the
    arch module sizes, and its step's work is counted by the module."""
    from bench_smoke import REASON_FORK
    from repro.models.registry import get_smoke
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "bench/tests/data/arch_sparse_moe.py",
                tmp_path / "bench/arch/sparse_moe.py")
    (tmp_path / "bench/configs/moe-smoke.json").write_text(json.dumps(MOE))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "moe-smoke", "source": "test",
                            "file": "bench/configs/moe-smoke.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "moe-smoke.reason-fork-w8",
                              "config": "moe-smoke",
                              "traffic": "reason-fork-w8", "chips": 1,
                              "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    pc = get_smoke("phi3.5-moe-42b-a6.6b")
    assert set(pc.layer_kinds()) == {"moe"}
    with harness_at(tmp_path):
        import importlib
        from bench import run as run_copy
        from bench.lib import work
        moe = importlib.import_module("bench.arch.sparse_moe")
        assert Path(moe.__file__).parent == tmp_path / "bench/arch"
        _cell, _c, config, traffic = run_copy.resolve(
            run_copy.load_spec(tmp_path), "moe-smoke.reason-fork-w8",
            tmp_path)
        assert config == MOE
        traffic = {**traffic, **REASON_FORK}
        run_copy.check_widths(config, pc)
        h = run_copy.Harness(config, traffic, 9, False, pc, "TPU v5 lite")
        drv = importlib.import_module(
            f"bench.drivers.{traffic['kind']}").Driver(h)
        drv.setup()                      # check_params passes inside
        assert drv.params["layers"][0]["moe"]["router"].dtype == "float32"
        pages = int((16e9 * 0.001 - 2 * moe.param_count(config))
                    // moe.page_bytes(config, 16))
        assert drv.eng.pool.num_pages == h.num_pages() == pages
        assert moe.param_count(config) == pc.param_count()
        ctx = [90, 200, 370]
        assert work.decode_step_work(config, ctx) == \
            moe.decode_step_work(config, ctx)
        assert drv.eng.generation(drv.flows[0].reason).status == "running"
        drv.release()


def test_control_is_not_correct():
    """The control (the reference in float8 put in the program's place)
    goes through the harness's own comparison and comes out not
    correct, while the program's reading at the same positions keeps
    under the limit."""
    from bench_smoke import medium_overrides
    res = run.run_cell(CELL, 22, 3.0, False, allow_cpu=True, control=True,
                       overrides=medium_overrides(),
                       log=lambda *a, **k: None)
    limit = res["checks"]["widest_gap"]["limit"]
    assert not res["correct"], res["checks"]
    assert res["checks"]["widest_gap"]["value"] > 2 * limit
    assert res["check_detail"]["program_widest_gap"] < limit
