"""Static DP-safety analysis: jaxpr taint + HLO rules.

Two cooperating passes over the compiled artifacts of
`repro.core.dp_sgd.make_dp_train_step`:

  * `jaxpr_taint` — walks the closed jaxpr and proves, per trainable
    leaf, that every batch-derived dataflow path is clip-factor-scaled
    before the parameter-update sink and that exactly one PRNG noise
    draw (with a leaf-unique key lineage) reaches it.
  * `rules` — named, severity-tagged rules over the post-SPMD HLO text
    (collective leaks across the model axis, backward-pass counts,
    donation coverage, shape stability), built on the `hlo` parser.

`repro.launch.audit` drives both over the clipping x execution x mesh
matrix and emits benchmarks/AUDIT.json.
"""
from repro.analysis.findings import (ERROR, INFO, WARNING, Finding, errors,
                                     worst_severity)

__all__ = ["ERROR", "INFO", "WARNING", "Finding", "errors", "worst_severity"]
