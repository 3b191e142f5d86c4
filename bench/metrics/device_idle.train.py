"""Share of the traced training window in which no operation ran on the
device: 1 - busy / window, busy being the union of the `XLA Ops` events."""


def read(run):
    s = run.summary
    if s.window_s <= 0 or not s.kernel_n:  # no device operation traced
        return None
    return 100.0 * (1.0 - s.busy_s / s.window_s)
