"""The train step's compute-bound roofline share: the FLOPs a step needs
over the chips' bf16 peak, divided by the device-busy time per step (device
trace, averaged over the chips)."""

from bench_cells.reduce import xplane


def read(run):
    r, trace = run["records"], run["trace"]
    if r.get("kind") != "train" or trace is None or not r["traced_steps"]:
        return None
    busy_per_step = xplane.busy_seconds(trace) / r["traced_steps"]
    least = (r["tokens_per_step"] * r["flops_per_token"]
             / (run["chips"] * run["peaks"]["bf16_flops_per_s"]))
    return 100.0 * least / busy_per_step
