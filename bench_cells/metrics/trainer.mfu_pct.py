"""Model FLOP/s utilization: window tokens/s times the FLOPs a token needs
(forward + backward, nothing recomputed; ``bench_cells/flops.py``) over
chips times the chip's bf16 peak. An end-to-end utilization, not a roofline
share."""


def read(run):
    r = run["records"]
    if r.get("kind") != "train":
        return None
    rate = r["tokens"] / r["window_s"]
    peak = run["chips"] * run["peaks"]["bf16_flops_per_s"]
    return 100.0 * rate * r["flops_per_token"] / peak
