"""Model FLOPs of prefilling one prompt of T tokens: every weight matrix of
the blocks once per token, the head once (only the last position's logits
are needed), and causal attention over the prompt (4 T^2 / 2 per head
dimension per layer)."""
from bench.work import decode_step


def flops(s: dict, T: int) -> float:
    head = s["d"] * s["V"]
    blocks = decode_step.matmul_params(s) - head
    return 2.0 * blocks * T + 2.0 * head \
        + 2.0 * s["L"] * s["H"] * s["hd"] * T * T
