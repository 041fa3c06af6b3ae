"""Training launcher: ``python -m repro.launch.train --arch <id> [opts]``.

Runs the full production loop on whatever devices the host exposes (the
512-chip mesh is exercised by ``dryrun.py``; this entry point trains for
real on the local mesh): sharded init or elastic restore, prefetching data
pipeline, async checkpoints, preemption drain, straggler watchdog.
"""

from __future__ import annotations

import sys


def main(argv=None) -> int:
    from repro.configs import (
        MeshConfig,
        RunConfig,
        TrainConfig,
        apply_overrides,
        get_model_config,
        get_shape,
        parse_cli,
    )
    from repro.configs.base import ShapeConfig

    overrides, _ = parse_cli(argv if argv is not None else sys.argv[1:])
    arch = overrides.pop("arch", "qwen2-0.5b")
    shape_name = overrides.pop("shape", "train_4k")
    reduced = overrides.pop("reduced", "true").lower() in ("1", "true", "yes")
    steps = int(overrides.pop("steps", "200"))
    seq_len = int(overrides.pop("seq_len", "256"))
    batch = int(overrides.pop("batch", "8"))

    cfg = get_model_config(arch)
    if reduced:
        cfg = cfg.reduced()
        shape = ShapeConfig(shape_name, seq_len, batch, "train")
    else:
        shape = get_shape(shape_name)

    run = RunConfig(model=cfg, shape=shape, train=TrainConfig(
        total_steps=steps, remat="none" if reduced else "full"))
    for k, v in list(overrides.items()):
        run = apply_overrides(run, {k: v})

    from repro.data import DataPipeline, SyntheticLMDataset
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_host_mesh
    from repro.launch.trainer import Trainer

    enable_compile_cache()
    mesh = make_host_mesh()
    print(f"[train] arch={arch} reduced={reduced} mesh={mesh.shape} "
          f"params={cfg.param_count() / 1e6:.1f}M")
    trainer = Trainer(run, mesh)
    start = trainer.init_or_restore()

    ds = SyntheticLMDataset(vocab_size=cfg.vocab_size, seq_len=shape.seq_len,
                            seed=run.train.seed)
    pipe = DataPipeline(ds, global_batch=shape.global_batch,
                        start_step=start)
    try:
        history = trainer.fit(steps - start, iter(pipe))
    finally:
        pipe.close()
    if history["loss"]:
        print(f"[train] loss {history['loss'][0]:.3f} -> "
              f"{history['loss'][-1]:.3f} over {len(history['loss'])} steps")
    return 0


if __name__ == "__main__":
    sys.exit(main())
