"""The architecture a configuration file names: its lookup, and pins that
keep the dense GQA cell's weights and pool what they were before the
shapes moved into ``bench/arch/dense_gqa.py``."""
import hashlib
import json

import numpy as np
import pytest

from bench_smoke import CONFIG, ROOT

from bench import run  # noqa: E402
from bench.arch import dense_gqa  # noqa: E402
from bench.lib import weights  # noqa: E402

QWEN2 = json.loads((ROOT / "bench/configs/qwen2-1.5b.json").read_text())
TRAFFIC = json.loads((ROOT / "bench/traffic/reason-fork-w8.json").read_text())


def harness(config, traffic=TRAFFIC):
    return run.Harness(config, traffic, 0, False, None, "TPU v5 lite")


def test_smoke_weights_are_the_parents_bit_for_bit():
    import jax
    h = hashlib.sha256()
    tree = weights.make_params(CONFIG, 2 ** 33 + 7)
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(a.dtype).encode())
        h.update(a.tobytes())
    assert h.hexdigest() == \
        "091de241791988c62eff9efa0fc82b4214bf5d2ce08d2ca5015f12c89f5acaeb"


def test_qwen2_leaf_order_is_the_parents():
    """Leaf i is drawn from fold_in(key, i): the published config's specs,
    in order, are those of the parent."""
    specs = dense_gqa.leaf_specs(QWEN2)
    assert len(specs) == 338
    assert hashlib.sha256(repr(specs).encode()).hexdigest() == \
        "c6a2bbb101185095509c0fa53eeb238b7b4daf4fce1d19eba19f74a69fa34fb8"


def test_qwen2_pool_on_v5e_is_14792_pages():
    assert dense_gqa.page_bytes(QWEN2, 16) == 460_544
    assert dense_gqa.state_bytes_per_row(QWEN2) == 0
    assert harness(QWEN2).num_pages() == 14_792 == int(
        (16e9 * 0.9 - 2 * 1_543_714_304 - 4.5e9) // 460_544)


def test_state_per_row_takes_max_batch_rows_out_of_the_pool(monkeypatch):
    state = 46 * 2 ** 20
    monkeypatch.setattr(dense_gqa, "state_bytes_per_row", lambda cfg: state)
    free = 16e9 * 0.9 - 2 * 1_543_714_304 - 4.5e9 - 40 * state
    assert TRAFFIC["max_batch"] == 40
    assert harness(QWEN2).num_pages() == int(free // 460_544) == 10_603
    smaller = harness(QWEN2, dict(TRAFFIC, max_batch=20)).num_pages()
    assert smaller == int((free + 20 * state) // 460_544)


@pytest.mark.parametrize("name", [None, "no_such_arch", "../run"])
def test_an_unknown_arch_is_a_cell_error(name):
    config = {k: v for k, v in CONFIG.items() if k != "arch"}
    if name is not None:
        config["arch"] = name
    with pytest.raises(run.CellError, match="bench/arch"):
        run.check_widths(config, None)
    with pytest.raises(run.CellError, match="bench/arch"):
        weights.make_params(config, 0)
    with pytest.raises(run.CellError, match="bench/arch"):
        harness(dict(QWEN2, arch=name) if name else config).num_pages()
