"""How the benchmark counts the operations an algorithm needs. Kept with
the benchmark so that no later PR can move the yardstick; the counts of one
network's layers and kernels, from the configuration's shapes, are in its
file under `networks/`.

Model FLOPs count every multiply-accumulate of a convolution or matrix
product as 2, forward and backward, and nothing else (elementwise work is
under 1% here). The backward pass of a layer costs twice its forward pass
(gradient of the input and of the weights), except a layer whose input is
data and needs no gradient. Recomputation is never counted.
"""

from __future__ import annotations


def step_flops(
    forward_macs: float, data_fed_macs: float, unroll_length: int, batch_size: int
) -> float:
    """Model FLOPs of one learner step, from the multiply-accumulates of
    one observation's forward pass (`data_fed_macs` of them in layers fed
    by data): the forward pass over T+1 observations of each unroll (the
    last one only bootstraps the value), the backward pass over the T that
    are trained on."""
    fwd = 2.0 * forward_macs
    bwd = 2.0 * fwd - 2.0 * data_fed_macs
    return batch_size * ((unroll_length + 1) * fwd + unroll_length * bwd)


def least_seconds(n_flops: float, n_bytes: float, peaks: dict) -> float:
    """The least time a chip with these peaks could take: the larger of
    FLOPs over the peak rate and bytes over the peak bandwidth."""
    return max(
        n_flops / peaks["bf16_flops_per_s"], n_bytes / peaks["hbm_bytes_per_s"]
    )
