"""The whole step's share of the chips' bf16 peak: the algorithm's
operations (``benchmark/lib/flops.py``) done in this run's window, over
the window and the peak of all chips used."""
from benchmark.lib import flops


def reduce(ctx, kind):
    win, peak = ctx["window"], ctx["peaks"]["bf16_flops_per_s"]
    if kind == "train":
        if not win.get("samples_per_s"):
            return None
        per_sample = flops.train_flops_per_sample(ctx["cfg"], ctx["traffic"])
        return flops.mfu_percent(per_sample * win["samples_per_s"], 1.0,
                                 ctx["chips"], peak)
    if not win.get("serve_flops"):
        return None
    return flops.mfu_percent(win["serve_flops"], win["seconds"], ctx["chips"],
                             peak)
