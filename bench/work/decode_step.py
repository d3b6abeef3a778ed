"""Model FLOPs of one decoded token of a dense decoder with X-PEFT adapters:
every weight matrix once (2 FLOPs per parameter per token, the head
included), plus attention scores and values against ``context`` cached
positions. The same count as the paper-era analytic model, restated here
so that no change to the program can move it."""


def matmul_params(s: dict) -> int:
    d, H, KV, hd, ff, L = (s[k] for k in ("d", "H", "KV", "hd", "ff", "L"))
    per_layer = d * H * hd + 2 * d * KV * hd + H * hd * d + 3 * d * ff \
        + 2 * d * s["b"]
    return L * per_layer + d * s["V"]


def flops_per_token(s: dict, context: float) -> float:
    return 2.0 * matmul_params(s) + 4.0 * s["L"] * s["H"] * s["hd"] * context
