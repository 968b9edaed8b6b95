"""mfu_pct.<cell kind>: the whole round's share of the chip's peak on
the resource that bounds it. The round's least time is the larger of
its model FLOPs over the bf16 peak and its least HBM bytes over the HBM
bandwidth (``work/<config>.py``, from shapes); the share is that time
times the rounds, over the traced window's wall time. Small for LeNet
by nature; printed unrounded."""


def read(run):
    t = run["trace"]
    rounds = run["counts"]["rounds"]
    if t is None or not rounds:
        return None
    w, p = run["work"], run["peaks"]
    least_s = max(w["flops"] / p["bf16_flops_per_s"],
                  w["bytes"] / p["hbm_bytes_per_s"])
    return 100.0 * least_s * rounds / t.window_s
