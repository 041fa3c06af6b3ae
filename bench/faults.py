"""Faults planted in the served path underneath the harness, to show that
``correct`` comes out false when the timed path is wrong.

Each takes ``patch(obj, name, value)`` (``setattr``, or pytest's
``monkeypatch.setattr``) and replaces one function of the program:

- ``token_altered``: every sampled token is shifted by one where the
  engine samples it;
- ``state_unchanged``: the decode step hands back its K/V pool as it got
  it: no decoded token's K/V is ever written, and the paged kernel reads
  the pool without it.  Where the K/V is written depends on the model,
  so each family module (``bench/families/<family>.py``) plants its own.

A served cell has no training step, no batch mean and no exchange
between chips, so these two are the faults it can have.  Plant a fault
before the engine is built: the engine compiles what it finds then.
"""

from __future__ import annotations

from typing import Any, Callable

Patch = Callable[[Any, str, Any], None]

#: The faults a served cell can have.
NAMES = ("state_unchanged", "token_altered")


def token_altered(patch: Patch) -> None:
    import repro.serve.engine as eng

    real = eng.sample

    def sample(logits, cfg, key=None):
        tok = real(logits, cfg, key)
        return (tok + 1) % logits.shape[-1]

    patch(eng, "sample", sample)


def plant(name: str, fam, patch: Patch) -> None:
    """Plant the fault ``name`` of ``NAMES`` in a model of the family
    module ``fam``."""
    if name == "token_altered":
        token_altered(patch)
    elif name == "state_unchanged":
        fam.state_unchanged(patch)
    else:
        raise ValueError(f"no fault {name!r}; known: {NAMES}")
