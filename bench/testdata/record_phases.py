"""Record `phases.xplane.pb.gz` and `phases.hlo.txt.gz`, the small chip
trace and compiled HLO that bench/tests/test_phases.py reduces by hand
(gzipped: the trace carries the step's HloProto): four steps of a
two-layer `tiny` private training step (per_layer clipping with the
`ghost_norm` and `clip_reduce` Pallas kernels, noise, Adam), each in a
`bench.step` span holding `bench.sample` (a 1 ms sleep: the device idles),
`bench.batch`, `bench.put`, `bench.dispatch` and `bench.fetch`, all inside
one `bench.window`, as the benchmark's driver lays them out.

    python bench/testdata/record_phases.py   # on a TPU, from the repo root

The files land in chiprun_out/phases_trace/.
"""
import dataclasses
import glob
import gzip
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.join(os.getcwd(), "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro import optim  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core.dp_sgd import DPConfig, make_dp_train_step  # noqa: E402
from repro.core.spec import init_params  # noqa: E402
from repro.kernels import backend as KB  # noqa: E402
from repro.models.transformer import build_model  # noqa: E402

B, T, STEPS = 4, 128, 4


def main(out="chiprun_out/phases_trace") -> None:
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_phases: needs a TPU")
    cfg = dataclasses.replace(get_config("tiny"), num_layers=2)
    m = build_model(cfg)
    dpc = DPConfig(mode="per_layer", sigma=1.0, sampling_rate=0.1, steps=10,
                   backend="pallas", autotune=False)
    init_fn, step_fn, _ = make_dp_train_step(
        m.loss_fn, m.spec, m.layout, optim.adam(1e-3), dpc, batch_size=B)
    params = init_params(m.spec, jax.random.PRNGKey(0))
    state = (params,) + tuple(init_fn(params))
    rng = np.random.default_rng(0)

    def batch():
        tok = rng.integers(0, cfg.vocab_size, (B, T), dtype=np.int32)
        return {"tokens": tok, "targets": np.roll(tok, -1, axis=1)}

    key = jax.random.PRNGKey(1)
    # the clipped sums as separate kernels, as the qwen3-4b cell runs them
    with KB.scoped("pallas", prefer_fused=False):
        compiled = jax.jit(step_fn).lower(
            *state, jax.device_put(batch()), key).compile()
    *state, met = compiled(*state, jax.device_put(batch()), key)
    jax.block_until_ready(met)
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out)
    with gzip.open(os.path.join(out, "phases.hlo.txt.gz"), "wt") as fh:
        fh.write(compiled.as_text())
    trace_dir = os.path.join(out, "trace")
    with jax.profiler.trace(trace_dir):
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(STEPS):
                with jax.profiler.TraceAnnotation("bench.step"):
                    with jax.profiler.TraceAnnotation("bench.sample"):
                        time.sleep(0.001)
                    with jax.profiler.TraceAnnotation("bench.batch"):
                        host = batch()
                    with jax.profiler.TraceAnnotation("bench.put"):
                        dev = jax.device_put(host)
                    with jax.profiler.TraceAnnotation("bench.dispatch"):
                        *state, met = compiled(*state, dev, key)
                    with jax.profiler.TraceAnnotation("bench.fetch"):
                        float(jax.device_get(met).loss)
    found = glob.glob(trace_dir + "/**/*.xplane.pb", recursive=True)[0]
    dest = os.path.join(out, "phases.xplane.pb.gz")
    with open(found, "rb") as src, gzip.open(dest, "wb") as fh:
        shutil.copyfileobj(src, fh)
    print(dest, os.path.getsize(dest))


if __name__ == "__main__":
    main()
