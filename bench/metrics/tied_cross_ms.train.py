"""Device milliseconds per training step of a tied embedding / LM head's
cross term: the ops whose op_name holds the `dp_tied_cross` scope
(core.bk.TIED_CROSS: the gather of the head's logit gradient at the
example's own tokens, and its T x T contraction with the embedding's output
gradients and the head's inputs), summed inside the step program's runs in
the traced window, over the `bench.step` spans (bench/phases.py). Nothing
where the step has no such op: a program without a tied group."""
import re

from bench import phases

SCOPE = re.compile(r'op_name="[^"]*\bdp_tied_cross\b')


def read(run):
    st = phases.of(run)
    if run.peak is None or st is None or not st.steps or st.module is None:
        return None
    from repro.analysis.hlo import CONTAINER_OPS, parse_module
    text = phases.hlo_text(st.module)
    names = {ins.name for instrs in parse_module(text).values()
             for ins in instrs
             if ins.op not in CONTAINER_OPS and SCOPE.search(ins.rest)}
    if not names:
        return None
    spent = sum(s for k, s in st.op_s.items() if k in names)
    return 1e3 * spent / st.devices / st.steps
