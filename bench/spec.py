"""What a cell is, read from data: ``BENCHMARK.json`` names the cell's
configuration and traffic mix, and the harness finds each by name, every
file under the one root it is given:

- ``bench/configs/<config>.json``: the model as it is run (published
  keys of its ``config.json``, cuts, departures, ``family``, ``dtype``,
  ``max_len``);
- ``bench/families/<family>.py``: what the benchmark knows of a family of
  models, loaded by its path (below);
- ``bench/traffic/<traffic>.json``: the mix (``bench/traffic.py``);
- ``bench/cells/<workload>.json``: what belongs to the pairing, the
  decode slots and the correctness sample and limit.

A family module provides

- ``desc(config)``: a frozen, hashable description of the model with
  ``name``, ``vocab``, ``max_len``, ``dtype``, ``n_layers`` and ``dry()``,
  the same kind of model at the CPU rehearsal's width;
- ``model_config(desc, repro_arch)``: the program's ``ModelConfig``, the
  one hook that imports the program to build the model;
- ``leaf_shapes(desc)``: ``path -> (shape, kind, scale)`` of the weight
  tree (``bench/weights.py``);
- ``logits(w, tokens, at, desc, fp8)``: the plain float32 reference
  forward, importing nothing of the program (``bench/reference.py``);
- ``prefill_chunk(desc, start, c, last, dtype_bytes)`` and
  ``decode_row(desc, ctx, dtype_bytes)``: the ``flops.Work`` of one chunk
  and one decode row (``bench/flops.py``);
- ``state_unchanged(patch)``: the fault of a decode step that leaves its
  K/V unwritten (``bench/faults.py``).

A configuration of a new family is then added with new files alone.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import sys
from pathlib import Path
from types import ModuleType
from typing import Any, Optional, Tuple

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent

#: What a family module has to provide.
FAMILY_HOOKS = ("desc", "model_config", "leaf_shapes", "logits",
                "prefill_chunk", "decode_row", "state_unchanged")


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    family: ModuleType
    model: Any
    mix: dict
    cell: dict


def load_benchmark(root: Optional[Path] = None) -> dict:
    return json.loads(((root or ROOT) / "BENCHMARK.json").read_text())


@functools.lru_cache(maxsize=None)
def _module(path: Path) -> ModuleType:
    # Registered, since a dataclass looks its module up in ``sys.modules``.
    name = f"bench_family_{path.stem}"
    modspec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(modspec)
    sys.modules[name] = mod
    modspec.loader.exec_module(mod)
    return mod


def load_family(family: str, root: Optional[Path] = None) -> ModuleType:
    """``bench/families/<family>.py`` under ``root``, loaded by its path
    (once a process: its jitted reference then compiles once)."""
    rel = f"bench/families/{family}.py"
    path = (root or ROOT) / rel
    if not path.is_file():
        raise SystemExit(f"no module for the {family!r} family: add {rel} "
                         f"with {', '.join(FAMILY_HOOKS)}")
    mod = _module(path.resolve())
    missing = [h for h in FAMILY_HOOKS if not hasattr(mod, h)]
    if missing:
        raise SystemExit(f"{rel} lacks {', '.join(missing)}")
    return mod


def load_config(name: str, root: Optional[Path] = None
                ) -> Tuple[dict, ModuleType]:
    """The configuration ``name`` of ``BENCHMARK.json`` and its family's
    module.  The family the file states has to be the one the program
    registers for the file's ``repro_arch``."""
    from repro.configs import get_model_config

    root = root or ROOT
    conf = next((c for c in load_benchmark(root)["configs"]
                 if c["name"] == name), None)
    if conf is None:
        raise SystemExit(f"no configuration {name!r} in BENCHMARK.json")
    config = json.loads((root / conf["file"]).read_text())
    registered = get_model_config(config["repro_arch"]).family
    if config["family"] != registered:
        raise SystemExit(f"{conf['file']} states the {config['family']!r} "
                         f"family; the program registers "
                         f"{config['repro_arch']} as {registered!r}")
    return config, load_family(config["family"], root)


def load_cell(workload: str, root: Optional[Path] = None) -> Cell:
    root = root or ROOT
    bench = load_benchmark(root)
    entry = next((w for w in bench["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        names = [w["name"] for w in bench["workloads"]]
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json; "
                         f"known: {names}")
    config, fam = load_config(entry["config"], root)
    mix = json.loads((root / "bench" / "traffic" /
                      f"{entry['traffic']}.json").read_text())
    cell = json.loads((root / "bench" / "cells" /
                       f"{workload}.json").read_text())
    return Cell(name=workload, chips=int(entry["chips"]), config=config,
                family=fam, model=fam.desc(config), mix=mix, cell=cell)
