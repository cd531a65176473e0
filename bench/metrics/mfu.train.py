"""Model FLOP utilization of training: model FLOPs per token (6 N_matmul
and 3 x the SFA attention forward, ``work.train_flops_per_token``) times
the traced window's tokens per second, over chips x peak."""


def read(r):
    h = r.host
    if not h.get("traced_s"):
        return None
    rate = h["traced_tokens"] / h["traced_s"]
    return (100.0 * h["model_flops_per_token"] * rate
            / (r.chips * r.peaks["bf16_flops"]))
