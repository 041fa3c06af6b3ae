"""The family modules (``bench/families/<family>.py``): what each cell
reads is what it read before the dense code moved there, and a family is
added to the benchmark through new files alone.

The literal values below were recorded from the dense code as it stood
in ``bench/weights.py``, ``bench/flops.py`` and ``bench/spec.py`` before
it moved into ``bench/families/dense.py``.
"""

import hashlib
import json
import shutil
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import flops, harness, spec, weights

#: ``(chunk, [(prompt_len, served), ...])`` at each configuration's
#: planned chunk on a TPU v5e.
PAIRS = {
    "stablelm-1.6b": (1024, [(64, 1024), (384, 263), (2176, 57), (1000, 1)]),
    "deepseek-coder-33b-stage8": (1176, [(2072, 59), (4200, 199),
                                         (10920, 622), (1176, 1)]),
}

#: ``flops.call_work`` over ``PAIRS`` in bf16: (prefill, decode), each
#: ``(attn_flops, attn_bytes, model_flops, rows)``.
WORK = {
    "stablelm-1.6b": [
        (579025502208.0, 2028994560.0, 9518362394624.0, 3624),
        (166676594688.0, 166940246016.0, 4025125896192.0, 1341)],
    "deepseek-coder-33b-stage8": [
        (16352321470464.0, 6818889728.0, 172208065347584.0, 18368),
        (1822997643264.0, 260629397504.0, 9669954273280.0, 877)],
}

_OUT_24 = 0.14433756729740646      # 1 / sqrt(2 * 24)
SHAPES = {
    "stablelm-1.6b": {
        "embedding": ((100352, 2048), "embed", 1.0),
        "final_norm": ((2048,), "norm", 1.0),
        "lm_head": ((2048, 100352), "proj", 1.0),
        "layers/ln1": ((24, 2048), "norm", 1.0),
        "layers/ln2": ((24, 2048), "norm", 1.0),
        "layers/attn/wq": ((24, 2048, 2048), "proj", 1.0),
        "layers/attn/wk": ((24, 2048, 2048), "proj", 1.0),
        "layers/attn/wv": ((24, 2048, 2048), "proj", 1.0),
        "layers/attn/wo": ((24, 2048, 2048), "proj", _OUT_24),
        "layers/ffn/wg": ((24, 2048, 5632), "proj", 1.0),
        "layers/ffn/wi": ((24, 2048, 5632), "proj", 1.0),
        "layers/ffn/wo": ((24, 5632, 2048), "proj", _OUT_24),
        "layers/attn/bq": ((24, 2048), "bias", 1.0),
        "layers/attn/bk": ((24, 2048), "bias", 1.0),
        "layers/attn/bv": ((24, 2048), "bias", 1.0),
    },
    "deepseek-coder-33b-stage8": {
        "embedding": ((32256, 7168), "embed", 1.0),
        "final_norm": ((7168,), "norm", 1.0),
        "lm_head": ((7168, 32256), "proj", 1.0),
        "layers/ln1": ((8, 7168), "norm", 1.0),
        "layers/ln2": ((8, 7168), "norm", 1.0),
        "layers/attn/wq": ((8, 7168, 7168), "proj", 1.0),
        "layers/attn/wk": ((8, 7168, 1024), "proj", 1.0),
        "layers/attn/wv": ((8, 7168, 1024), "proj", 1.0),
        "layers/attn/wo": ((8, 7168, 7168), "proj", 0.25),
        "layers/ffn/wg": ((8, 7168, 19200), "proj", 1.0),
        "layers/ffn/wi": ((8, 7168, 19200), "proj", 1.0),
        "layers/ffn/wo": ((8, 19200, 7168), "proj", 0.25),
    },
}

#: sha256 of the dry model's bf16 weights from seed ``2**33 + 5`` (each
#: leaf's path, then its bytes, in path order).
WEIGHTS_SHA256 = {
    "stablelm-1.6b":
        "5da1ded920c09001a327db34b1640639c050870c0ab76543d53732facb1a3302",
    "deepseek-coder-33b-stage8":
        "9679da68d19d1caca7f7dae7666fcedf56969051735b791aa54e88023a019485",
}


@pytest.mark.parametrize("name", sorted(PAIRS))
def test_leaf_shapes_as_before(name):
    conf, fam = spec.load_config(name)
    assert fam.leaf_shapes(fam.desc(conf)) == SHAPES[name]


@pytest.mark.parametrize("name", sorted(PAIRS))
def test_call_work_as_before(name):
    conf, fam = spec.load_config(name)
    chunk, pairs = PAIRS[name]
    pre, dec = flops.call_work(fam, fam.desc(conf), pairs, chunk, 2)
    got = [(w.attn_flops, w.attn_bytes, w.model_flops, w.rows)
           for w in (pre, dec)]
    assert got == WORK[name]


@pytest.mark.parametrize("name", sorted(PAIRS))
def test_dry_weights_as_before(name):
    conf, fam = spec.load_config(name)
    w = weights.make_weights(fam, fam.desc(conf).dry(), 2 ** 33 + 5,
                             jnp.bfloat16)
    h = hashlib.sha256()
    leaves = jax.tree_util.tree_flatten_with_path(w)[0]
    for path, leaf in sorted(leaves,
                             key=lambda kv: jax.tree_util.keystr(kv[0])):
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest() == WEIGHTS_SHA256[name]


def _stage(root, family_file, family, repro_arch):
    """A tree under ``root`` with one configuration (a copy of
    stablelm-1.6b's stating ``family`` and ``repro_arch``), one cell and
    one mix, each a new file, and ``family_file`` as
    ``bench/families/<family>.py`` where given."""
    src = spec.ROOT / "bench"
    for d in ("configs", "cells", "traffic", "families"):
        (root / "bench" / d).mkdir(parents=True)
    conf = json.loads((src / "configs" / "stablelm-1.6b.json").read_text())
    conf.update(name="staged", family=family, repro_arch=repro_arch)
    (root / "bench" / "configs" / "staged.json").write_text(json.dumps(conf))
    shutil.copy(src / "cells" / "stablelm-longdoc.json",
                root / "bench" / "cells" / "staged-cell.json")
    shutil.copy(src / "traffic" / "longdoc.json",
                root / "bench" / "traffic" / "staged-mix.json")
    if family_file is not None:
        (root / "bench" / "families" / f"{family}.py").write_text(family_file)
    (root / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "staged", "file": "bench/configs/staged.json"}],
        "workloads": [{"name": "staged-cell", "config": "staged",
                       "traffic": "staged-mix", "chips": 1}]}))


def test_family_without_module_names_the_file_to_add(tmp_path):
    _stage(tmp_path, None, "mla_moe", "deepseek-v2-236b")
    with pytest.raises(SystemExit, match="bench/families/mla_moe.py"):
        spec.load_cell("staged-cell", root=tmp_path)


def test_family_has_to_be_the_registered_one(tmp_path):
    dense = (spec.BENCH_DIR / "families" / "dense.py").read_text()
    _stage(tmp_path, dense, "dense", "deepseek-v2-236b")
    with pytest.raises(SystemExit, match="'mla_moe'"):
        spec.load_cell("staged-cell", root=tmp_path)


def test_family_added_with_new_files_alone(tmp_path):
    """A family module, configuration, cell and mix staged as new files
    of their own tree are what the cell loads, and a dry run of it
    compares what it served with that module's reference."""
    dense = (spec.BENCH_DIR / "families" / "dense.py").read_text()
    _stage(tmp_path, dense + '\nMARKER = "staged"\n', "dense",
           "stablelm-1.6b")
    cell = spec.load_cell("staged-cell", root=tmp_path)
    assert cell.family.MARKER == "staged"
    assert not hasattr(spec.load_family("dense"), "MARKER")
    assert cell.model.name == "staged"
    assert cell.mix["prompt"]["median"] == 2048
    res = harness.run(cell, 99, 1.0, False, t_process=time.perf_counter(),
                      dry=True, log=lambda _m: None)
    assert res.correct, res.checks
    assert res.sample and res.checks["max_logit_gap"]["value"] <= \
        res.checks["max_logit_gap"]["limit"]
