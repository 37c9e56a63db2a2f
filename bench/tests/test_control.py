"""The control, kept at a size a test run can hold: the float32 reference
put in the program's place at float8 (``bench/model.py``) picks the token
at every position of the run's sampled answers, and the harness's own
comparison must then decide ``correct`` false, where the program's reading
on the same answers passes the ``logit_gap`` limit.  On the chip the same
run is made at each cell's own size by ``bench/calibrate.py``; here the
tiny cell's limit sits between the two readings of the tiny model."""
import time

import pytest

from bench import run
from bench.tests import tiny


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return tiny.make_tree(str(tmp_path_factory.mktemp("control_tree")))


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_fails_where_the_program_passes(tree, seed):
    out = run.run_cell("tiny.tiny-stream", seed, 3.0, False, bench_dir=tree,
                       require_tpu=False, t_start=time.monotonic(),
                       control=True)
    gap = out["checks"]["logit_gap"]
    assert out["correct"] is False, out["checks"]
    assert gap["value"] > gap["limit"]
    assert out["program"]["logit_gap"] <= gap["limit"]
    others = {k: v for k, v in out["checks"].items() if k != "logit_gap"}
    assert all(v["value"] <= v["limit"] for v in others.values()), others
