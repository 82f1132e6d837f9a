"""Process start to the start of the window: imports, weights, tracing and
lowering, compilation or the cache read, warm-up."""


def read(run):
    return run["setup"]["setup_s"]
