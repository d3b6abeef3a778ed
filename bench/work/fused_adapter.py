"""Fused bottleneck adapter (one launch over B rows of T tokens):
y = x + B̂ act(LN(x Â)). Two matmuls per token and the residual add; x is
read and y written once, and each row's Â, B̂ and LN affine are read
once."""


def work(B: int, T: int, d: int, b: int, itemsize: int = 2) -> tuple:
    flops = 4.0 * B * T * d * b + B * T * d
    nbytes = (2 * B * T * d * itemsize + 2 * B * d * b * itemsize
              + 2 * B * b * 4)
    return flops, nbytes
