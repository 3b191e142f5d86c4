"""Record `probe.xplane.pb`, the small chip trace that bench/tests/
test_trace.py reduces by hand: three steps of one jitted program (a
`ghost_norm` Pallas kernel and a matmul fusion) between host spans
`bench.sample` (a 2 ms sleep: the device idles), `bench.dispatch` and
`bench.fetch`.

    python bench/testdata/record_probe.py   # on a TPU, from the repo root
"""
import glob
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.join(os.getcwd(), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.kernels.ghost_norm import ghost_norm  # noqa: E402


@jax.jit
def prog(a, g, w):
    return ghost_norm(a, g), jnp.tanh(a @ w).sum()


def main(out="chiprun_out/probe_trace") -> None:
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_probe: needs a TPU")
    key = jax.random.PRNGKey(0)
    a = jax.random.normal(key, (4, 512, 1024), jnp.bfloat16)
    g = jax.random.normal(key, (4, 512, 2048), jnp.bfloat16)
    w = jax.random.normal(key, (1024, 4096), jnp.bfloat16)
    jax.block_until_ready(prog(a, g, w))
    shutil.rmtree(out, ignore_errors=True)
    with jax.profiler.trace(out):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.sample"):
                time.sleep(0.002)
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                res = prog(a, g, w)
            with jax.profiler.TraceAnnotation("bench.fetch"):
                jax.block_until_ready(res)
    print(glob.glob(out + "/**/*.xplane.pb", recursive=True)[0])


if __name__ == "__main__":
    main()
