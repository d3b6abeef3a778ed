"""k-sparse bank aggregation (one launch): each of ``rows`` output rows
[d, b] is the weighted sum of its k selected bank rows, accumulated in
float32. The bank rows are read once; the output is written once."""


def work(rows: int, k: int, d: int, b: int, itemsize: int = 2) -> tuple:
    flops = 2.0 * rows * k * d * b
    nbytes = rows * k * d * b * itemsize + rows * d * b * 4 + rows * k * 8
    return flops, nbytes
