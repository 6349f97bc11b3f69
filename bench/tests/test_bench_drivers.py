"""The harness and the traffic drivers at smoke size on the CPU: seeded
schedules, the refusal of the CPU by the measurement path, the
data-driven lookup of cells and metrics, and faults planted under the
timed path that the comparison with the reference must catch."""
import json
import shutil

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
