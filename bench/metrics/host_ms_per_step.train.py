"""Host milliseconds per training step outside the compiled call and its
metrics fetch: drawing the Poisson sample, building the batch and copying
it to the device (the harness's own spans, host clock)."""


def read(run):
    n = run.stats.get("steps", 0)
    host = run.stats.get("host_s", {})
    if not n or "batch" not in host:
        return None
    spent = sum(host.get(k, 0.0) for k in ("sample", "batch", "put"))
    return 1e3 * spent / n
