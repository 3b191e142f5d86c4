"""Device milliseconds per training step in the backward phase: the
transposed ops under the step's clip scope (core.dp_sgd.PHASE_CLIP), with
the per-example norms, the clipped sums and the forward ops recomputed in
the backward, by the compiled step's own map (repro.analysis.hlo.op_phases).

Their summed device time inside the step program's runs in the traced
window, over the `bench.step` spans; nothing where the map leaves more than
2 % of the step's op time unattributed (bench/phases.py)."""
from bench import phases


def read(run):
    return phases.phase_ms(run, "backward")
