"""The bfloat16 control fails every cell's check, and the float32
reference agrees with itself: a limit must sit between the two."""
import os

import pytest

from chipbench import controls
from chipbench.run import BENCH_DIR, load_json, load_module

HERE = os.path.dirname(os.path.abspath(__file__))
# workload files; the serving cell is the tests' own template
CELLS = [os.path.join(BENCH_DIR, "workloads", "jag_icf.b10.json"),
         os.path.join(BENCH_DIR, "workloads", "seir_covid.b32.json"),
         os.path.join(HERE, "serve_cell.json")]


@pytest.mark.parametrize("path", CELLS, ids=os.path.basename)
def test_bfloat16_control_fails_the_check(path):
    cell = load_json(path)
    config = load_json(BENCH_DIR, "configs", cell["config"] + ".json")
    ref = load_module("reference", config["name"])
    control = controls.serve_control if cell["driver"] == "serve_open_loop" \
        else controls.study_control
    res = control(cell, config, ref, seed=2 ** 31 + 7)
    limit = ref.LIMITS["reply_gap" if "serve" in cell["driver"] else "value_gap"]
    assert res["value_gap"] > 3 * limit, res


@pytest.mark.parametrize("config", ["jag_icf", "seir_covid"])
def test_reference_matches_itself(config):
    import numpy as np
    cfg = load_json(BENCH_DIR, "configs", config + ".json")
    ref = load_module("reference", config)
    ids = np.arange(2 ** 31 - 8, 2 ** 31 + 8) % (2 ** 32)
    u = np.random.default_rng(0).random((len(ids), cfg["input_dims"]),
                                        dtype=np.float32)
    a = ref.simulate(ids, u)
    assert set(a) == set(cfg["outputs"])
    assert ref.gap(a, ref.simulate(ids, u)) == {"value_gap": 0.0,
                                                "nan_mismatch": 0}
