"""Record the small trace that ``bench/tests/test_trace.py`` reads.

    python3 bench/testdata/record_trace.py [OUT]   # on a TPU

It writes ``paged_decode.xplane.pb`` into ``OUT`` (default: beside
this script).

Three runs of a jitted ``decode_fn`` that calls the repo's paged
attention kernel over a small pool, so the trace has the names the
benchmark reduces by: the program ``jit_decode_fn`` and the kernel's
operation.
"""

import glob
import os
import shutil
import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent.parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.kernels.paged_attention import paged_attention  # noqa: E402

SLOTS, PAGES, T, KV, D, H = 4, 9, 256, 8, 128, 32


def decode_fn(q, k, v, table, lengths):
    return paged_attention(q, k, v, table, lengths, page_tokens=T) * 2.0


def main() -> int:
    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 1
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (SLOTS, H, D), jnp.bfloat16)
    k = jax.random.normal(key, (PAGES, T, KV, D), jnp.bfloat16)
    table = jnp.arange(1, SLOTS * 2 + 1, dtype=jnp.int32).reshape(SLOTS, 2)
    lengths = jnp.array([100, 300, 512, 1], jnp.int32)
    f = jax.jit(decode_fn)
    f(q, k, k, table, lengths).block_until_ready()
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    for _ in range(3):
        f(q, k, k, table, lengths).block_until_ready()
    jax.profiler.stop_trace()
    src = sorted(glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                           recursive=True))[-1]
    out = Path(sys.argv[1] if len(sys.argv) > 1 else HERE)
    out.mkdir(parents=True, exist_ok=True)
    shutil.copy(src, out / "paged_decode.xplane.pb")
    shutil.rmtree(tmp)
    print(os.path.getsize(out / "paged_decode.xplane.pb"))
    return 0


if __name__ == "__main__":
    sys.exit(main())
