"""What a cell is, read from data: ``BENCHMARK.json`` names the cell's
configuration and traffic mix, and the harness finds each by name.

- ``bench/configs/<config>.json``: the model as it is run (published
  keys of its ``config.json``, cuts, departures, ``dtype``, ``max_len``);
- ``bench/traffic/<traffic>.json``: the mix (``bench/traffic.py``);
- ``bench/cells/<workload>.json``: what belongs to the pairing, the
  decode slots and the correctness sample and limit.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Optional

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


@dataclasses.dataclass(frozen=True)
class ModelDesc:
    """The dense decoder a configuration file describes."""

    name: str
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    n_layers: int
    rope_theta: float
    norm_eps: float
    qkv_bias: bool
    dtype: str
    max_len: int

    @classmethod
    def from_config(cls, c: dict) -> "ModelDesc":
        d, h = int(c["hidden_size"]), int(c["num_attention_heads"])
        eps = c.get("rms_norm_eps", c.get("layer_norm_eps"))
        return cls(
            name=c["name"], d_model=d, n_heads=h,
            n_kv_heads=int(c.get("num_key_value_heads", h)),
            head_dim=int(c.get("head_dim") or d // h),
            d_ff=int(c["intermediate_size"]), vocab=int(c["vocab_size"]),
            n_layers=int(c["num_hidden_layers"]),
            rope_theta=float(c["rope_theta"]), norm_eps=float(eps),
            qkv_bias=bool(c.get("use_qkv_bias", c.get("attention_bias",
                                                      False))),
            dtype=c["dtype"], max_len=int(c["max_len"]))

    def dry(self) -> "ModelDesc":
        """The CPU rehearsal's size: the same kind of model (MHA or GQA,
        bias or none) at a width the Pallas interpreter runs in seconds."""
        kv = 4 if self.n_kv_heads == self.n_heads else 2
        return dataclasses.replace(
            self, d_model=64, n_heads=4, n_kv_heads=kv, head_dim=16,
            d_ff=128, vocab=256, n_layers=2, max_len=512)


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    model: ModelDesc
    mix: dict
    cell: dict


def load_benchmark(root: Optional[Path] = None) -> dict:
    return json.loads(((root or ROOT) / "BENCHMARK.json").read_text())


def load_cell(workload: str, root: Optional[Path] = None) -> Cell:
    bench = load_benchmark(root)
    entry = next((w for w in bench["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        names = [w["name"] for w in bench["workloads"]]
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json; "
                         f"known: {names}")
    conf = next(c for c in bench["configs"] if c["name"] == entry["config"])
    config = json.loads(((root or ROOT) / conf["file"]).read_text())
    mix = json.loads((BENCH_DIR / "traffic" /
                      f"{entry['traffic']}.json").read_text())
    cell = json.loads((BENCH_DIR / "cells" / f"{workload}.json").read_text())
    return Cell(name=workload, chips=int(entry["chips"]), config=config,
                model=ModelDesc.from_config(config), mix=mix, cell=cell)


def model_config(m: ModelDesc, repro_arch: str):
    """The program's ``ModelConfig`` for ``m``: its registered entry
    ``repro_arch`` with every size and option the file states."""
    from repro.configs import get_model_config

    cfg = get_model_config(repro_arch)
    if cfg.family != "dense":
        raise SystemExit(f"{repro_arch} is a {cfg.family} model; the "
                         f"reference covers dense decoders only")
    return dataclasses.replace(
        cfg, n_layers=m.n_layers, d_model=m.d_model,
        n_heads=m.n_heads, n_kv_heads=m.n_kv_heads, d_ff=m.d_ff,
        vocab_size=m.vocab, d_head=m.head_dim, qkv_bias=m.qkv_bias,
        tie_embeddings=False, rope_theta=m.rope_theta, norm_eps=m.norm_eps,
        sliding_window=0)
