"""Model FLOPs of one X-PEFT training step over the onboarding roster: the
frozen decoder's forward and the backward pass to the activations (no
weight gradients: the PLM and the bank are frozen), plus what the mask
logits' gradients need: each example's dense mask aggregation of Â and B̂
over the bank, forward and backward, and each adapter's Â/B̂ gradient.

Per token of a sequence of T:
- forward: every weight matrix once (``decode_step.matmul_params``: blocks,
  adapters, head), causal attention 2 L H hd T;
- backward to the activations: every weight matrix once more, attention
  twice its forward;
- the adapter's Â/B̂ gradients: 2 d b per side per layer.
Per example: aggregation forward 2 L N d b per side, and the mask weights'
gradient against the bank 2 L N d b per side.
"""
from bench.work import decode_step


def flops_per_token(s: dict, T: int) -> float:
    M = decode_step.matmul_params(s)
    attn = 2.0 * s["L"] * s["H"] * s["hd"] * T
    fwd = 2.0 * M + attn
    bwd = 2.0 * M + 2.0 * attn + 2.0 * 2 * s["L"] * s["d"] * s["b"]
    return fwd + bwd


def flops_per_example(s: dict) -> float:
    return 2.0 * 2 * 2 * s["L"] * s["N"] * s["d"] * s["b"]


def flops(s: dict, examples: int, T: int) -> float:
    """One gang step over ``examples`` sequences of ``T`` tokens."""
    return examples * (T * flops_per_token(s, T) + flops_per_example(s))
