"""The float32 reference agrees with the program's ``Model.forward`` on the
CPU, at the rehearsal's width, for each configuration of
``BENCHMARK.json``; and its fp8 control does not."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference, spec, weights

CONFIGS = [c["name"] for c in spec.load_benchmark()["configs"]]


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_matches_model_forward(name):
    from repro.models.model import build_model

    conf, fam = spec.load_config(name)
    m = fam.desc(conf).dry()
    cfg = fam.model_config(m, conf["repro_arch"])
    w = weights.make_weights(fam, m, 2 ** 33 + 5, jnp.float32)
    toks = np.random.default_rng(0).integers(0, m.vocab, 300, np.int32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(build_model(cfg, remat="none").forward(
            w, {"tokens": jnp.asarray(toks)[None]}, dtype=jnp.float32)[0][0])
        at = jnp.arange(len(toks), dtype=jnp.int32)
        seq = jnp.asarray(reference._padded(toks, reference.BUCKET))
        got = np.asarray(fam.logits(w, seq, at, m, False))
        ctl = np.asarray(fam.logits(w, seq, at, m, True))
    # float32 throughout on both sides: the residue is summation order.
    assert np.abs(got - want).max() < 1e-4 * np.abs(want).max()
    assert np.abs(ctl - want).max() > 1e-2 * np.abs(want).max()


def test_gap_of_the_reference_argmax_is_zero():
    conf, fam = spec.load_config("stablelm-1.6b")
    m = fam.desc(conf).dry()
    w = weights.make_weights(fam, m, 7, jnp.bfloat16)
    prompt = np.random.default_rng(1).integers(0, m.vocab, 40, np.int32)
    served = []
    with jax.default_matmul_precision("highest"):
        for _ in range(5):
            seq = np.concatenate([prompt, np.asarray(served, np.int32)])
            lg = fam.logits(
                w, jnp.asarray(reference._padded(seq, reference.BUCKET)),
                jnp.asarray([len(seq) - 1], jnp.int32), m, False)
            served.append(int(jnp.argmax(lg[0])))
        prog, ctl = reference.gaps(fam, w, m, prompt, served, control=True)
    assert prog == 0.0
    assert ctl >= 0.0
