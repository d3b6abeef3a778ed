"""Operations and bytes each kernel or step needs, computed from shapes: the
work the algorithm requires, whatever implements it. A roofline share is
the least time these allow on the chip's peaks over the measured time."""


def least_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    """The roofline: the larger of compute time and memory time at peak."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
