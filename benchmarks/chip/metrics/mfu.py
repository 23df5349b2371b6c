"""Model FLOP utilisation of the window in percent: the benchmark's FLOP
count of one step (forward + backward, causal attention once, recomputation
not counted) times the steps traced, over the traced window, over the
chips' bf16 peak."""


def read(rec):
    t = rec.get("trace")
    if not t or not rec.get("steps_traced") or t["window_s"] <= 0:
        return None
    done = rec["flops_per_step"] * rec["steps_traced"]
    return 100.0 * done / t["window_s"] / (rec["chips"] * rec["peak_flops"])
