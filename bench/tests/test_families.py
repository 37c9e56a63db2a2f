"""Model families: a configuration brings its architecture as a file under
``bench/families/``, found by the name in its ``"family"`` key, and the
harness takes a new one with no edit of its own code.  The dense family's
weights and reference are pinned to checksums taken before its code moved
out of ``bench/model.py``, so moving it changed no number."""
import ast
import glob
import hashlib
import json
import os
import re
import time
import types

import jax.numpy as jnp
import numpy as np
import pytest

from bench import common, harness, model, run
from bench.tests import tiny

SEED = 2 ** 31 + 77
ROW = np.array([[(29 * i + 3) % 512 for i in range(24)]], np.int32)
# sha256 over each leaf's path, shape, dtype and bytes, in sorted path
# order, of the tiny dense weights at SEED; and of the (1, 24, 512) float32
# reference logits of ROW, and of the float8 control's
WEIGHTS_SHA256 = \
    "59a75c9d6464e097b115f00cf5accebf751d91968d78708697054421687d4dd7"
LOGITS_SHA256 = {
    False: "4bc9d9ce43103ce346f7f367cd4aaf2f575ee1b5fe448006ed9d62efceb8e06e",
    True: "6114bf1d1967322312cac9c0a975572a5099d07c4a8bc2f439e19f981b3dfee0",
}
FUNCTIONS = ("param_shapes", "logits", "program_config", "decode_weights",
             "decode_cost", "attention_layers")


def _digest(arrays):
    h = hashlib.sha256()
    for path, a in arrays:
        a = np.asarray(a)
        h.update(f"{path}:{a.shape}:{a.dtype}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


def _leaves(tree, prefix=""):
    for k in sorted(tree):
        p = f"{prefix}/{k}" if prefix else k
        if isinstance(tree[k], dict):
            yield from _leaves(tree[k], p)
        else:
            yield p, tree[k]


@pytest.fixture(scope="module")
def tiny_weights():
    return model.make_weights(tiny.TINY_CONFIG, SEED)


def test_tiny_dense_weights_are_bit_identical(tiny_weights):
    assert _digest(_leaves(tiny_weights)) == WEIGHTS_SHA256


@pytest.mark.parametrize("fp8", [False, True])
def test_tiny_dense_reference_logits_are_bit_identical(tiny_weights, fp8):
    c = tiny.TINY_CONFIG
    ref = common.load_family(c).logits(tiny_weights, jnp.asarray(ROW), c,
                                       fp8=fp8)
    assert ref.shape == (1, 24, 512)
    assert _digest([("logits", ref)]) == LOGITS_SHA256[fp8]


@pytest.mark.parametrize("path", sorted(glob.glob(
    os.path.join(common.BENCH_DIR, "configs", "*.json"))))
def test_each_configuration_names_a_family_file(path):
    with open(path) as f:
        c = json.load(f)
    assert os.path.exists(common.reader_path("families", c["family"]))
    assert common.load_family(c).param_shapes(c)


@pytest.mark.parametrize("path", sorted(glob.glob(
    os.path.join(common.BENCH_DIR, "families", "*.py"))))
def test_each_family_gives_the_harness_what_it_reads(path):
    name = os.path.basename(path)[:-3]
    mod = common.load_reader("families", name)
    assert mod.__doc__ and len(mod.__doc__) > 100
    for fn in FUNCTIONS:
        assert callable(getattr(mod, fn, None)), f"{name} has no {fn}"
    # the reference stays independent of the code under test: nothing of
    # the program is imported at the top of a family's file
    with open(path) as f:
        top = ast.parse(f.read()).body
    imported = [a.name for n in top if isinstance(n, ast.Import)
                for a in n.names]
    imported += [n.module or "" for n in top if isinstance(n, ast.ImportFrom)]
    assert not [m for m in imported if m.split(".")[0] == "repro"], imported


RECORDING = '''"""A recording family for the harness's own test: the dense family's
functions, each noting in ``CALLED`` that the harness called it.  Added to
a throwaway tree as a file, as a new architecture's family would be."""
import os

from bench import common

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
dense = common.load_reader("families", "dense", HERE)
CALLED = set()


def _noted(name):
    def call(*a, **kw):
        CALLED.add(name)
        return getattr(dense, name)(*a, **kw)
    return call


for _name in %r:
    globals()[_name] = _noted(_name)
''' % (FUNCTIONS,)


def _point_tiny_at(bench_dir, **change):
    c = dict(tiny.TINY_CONFIG, **change)
    c = {k: v for k, v in c.items() if v is not None}
    with open(os.path.join(bench_dir, "configs", "tiny.json"), "w") as f:
        json.dump(c, f)


def test_a_family_added_as_a_file_drives_a_served_cell(tmp_path):
    bench_dir = tiny.make_tree(str(tmp_path))
    with open(os.path.join(bench_dir, "families", "recording.py"), "w") as f:
        f.write(RECORDING)
    _point_tiny_at(bench_dir, family="recording")
    out = run.run_cell("tiny.tiny-stream", SEED, 3.0, False,
                       bench_dir=bench_dir, require_tpu=False,
                       t_start=time.monotonic())
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    cell = harness.load_cell("tiny.tiny-stream", bench_dir)
    fam = common.load_family(cell.config, bench_dir)
    assert fam.CALLED == {"param_shapes", "logits", "program_config"}
    # the step counts are read from a device trace, which the CPU has not:
    # the tree's own readers over a trace of one decode step of this cell,
    # one kernel call per attention layer of the family
    A = fam.attention_layers(cell.config)
    fam.CALLED.discard("attention_layers")
    kernel = "%c.1 = bf16[2,2,16]{2,1,0} custom-call(%x)"
    tr = types.SimpleNamespace(
        t0=0.0, t1=1.0, modules=[("%jit_decode_step", 0.2, 0.3)],
        ops=[(kernel, 0.2 + 0.01 * i, 0.205 + 0.01 * i) for i in range(A)])
    ctx = harness.Context(cell=cell, config=cell.config, records=[],
                          before={}, after={}, setup_s=0.0,
                          calls=[(0.1, 1, [40])], searches=[],
                          peaks=common.peaks("TPU v5 lite"), cache_dim=64,
                          trace=tr)
    read = lambda m: common.load_reader("metrics", m, bench_dir).read(ctx)
    shapes = fam.dense.param_shapes(cell.config)
    projections = [shapes["embed"]] + [s for k, s in shapes["layers"].items()
                                       if k.startswith("w")]
    assert ctx.kernel("decode_step").weights(cell.config) == sum(
        int(np.prod(s)) for s in projections)
    mfu = read("decode_step_mfu")
    flops = fam.dense.decode_cost(cell.config, 40)[0]
    assert mfu == pytest.approx(100 * flops / (0.1 * 197e12))
    assert 0 < read("paged_decode_attention_roofline") < 100
    assert fam.CALLED == set(FUNCTIONS)


@pytest.mark.parametrize("family,error", [(None, KeyError),
                                          ("nonesuch", FileNotFoundError)])
def test_a_configuration_without_a_family_file_fails_naming_it(
        tmp_path, family, error):
    bench_dir = tiny.make_tree(str(tmp_path))
    _point_tiny_at(bench_dir, family=family)
    where = re.escape(os.path.join(bench_dir, "families"))
    with pytest.raises(error, match=where):
        run.run_cell("tiny.tiny-stream", SEED, 3.0, False,
                     bench_dir=bench_dir, require_tpu=False,
                     t_start=time.monotonic())
