"""Device milliseconds per training step in noise plus update: the ops
under the step's noise or update scope (core.dp_sgd.PHASE_NOISE and
PHASE_UPDATE: clip counts, the noise draw and its addition, keys and
thresholds, the optimizer, the quantile update), by the compiled step's own
map (repro.analysis.hlo.op_phases). One phase, since XLA fuses the noise's
addition into the optimizer's elementwise fusion.

Their summed device time inside the step program's runs in the traced
window, over the `bench.step` spans; nothing where the map leaves more than
2 % of the step's op time unattributed (bench/phases.py)."""
from bench import phases


def read(run):
    return phases.phase_ms(run, "noise_update")
