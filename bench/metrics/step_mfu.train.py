"""Model FLOP utilisation of the training step over the traced window: the
model FLOPs of one step (bench/flops: forward and backward matmuls with the
LM head, and attention; no recomputation, no clipping work) times the steps
whose spans lie in the window, over window x chips x the peak bf16 rate."""


def read(run):
    steps = run.summary.span_n.get("bench.step", 0)
    if not steps or run.peak is None or not run.summary.kernel_n:
        return None
    tr = run.traffic
    flops = run.flops.train_step_flops(run.cfg, tr["batch"], tr["seq"])
    peak = run.peak["bf16_flops_per_s"] * run.chips
    return 100.0 * flops * steps / (run.summary.window_s * peak)
