"""Share of its roofline that the ghost-norm kernel reaches: the least time
(the larger of the FLOP and the byte bound, bench/flops) of the norm work
of every linear that `auto` sent to the Pallas kernels, over the summed
device time of the events whose HLO name holds `ghost_norm` in the traced
window (the compiler prefixes some, e.g. `transpose_jvp_..._`).

A linear goes to the kernel where the recorded choice for its shape is
`pallas`; each runs once per use per step. If the trace holds another
number of `ghost_norm*` events than that predicts, the work cannot be
attributed and the metric is left out."""

KERNELS = ("ghost_norm", "ghost_norm_blocked")
OPS = ("norms", "linear_clip")


def read(run):
    if run.peak is None:
        return None
    tr = run.traffic
    b, t = tr["batch"], tr["seq"]
    steps = run.summary.span_n.get("bench.step", 0)
    chosen = {(din, dout) for op, tt, din, dout, impl in run.stats["choices"]
              if op in OPS and tt == t and impl == "pallas"}
    least, events = 0.0, 0
    for _, din, dout, uses in run.flops.linears(run.cfg):
        if (din, dout) in chosen:
            flops, nbytes = run.flops.ghost_norm_cost(b, t, din, dout)
            least += uses * run.flops.least_time(flops, nbytes, run.peak)[0]
            events += uses
    names = [k for k in run.summary.kernel_s
             if any(kernel in k for kernel in KERNELS)]
    spent = sum(run.summary.kernel_s[k] for k in names)
    seen = sum(run.summary.kernel_n[k] for k in names)
    if not steps or not spent or seen != events * steps:
        return None
    return 100.0 * least * steps / spent
