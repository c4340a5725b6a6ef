"""Operations and bytes the algorithm needs, from the configuration's
shapes. Kept with the benchmark so that no later PR can move the yardstick.

Model FLOPs count every multiply-accumulate of a convolution or matrix
product as 2, forward and backward, and nothing else (elementwise work is
under 1% here). The backward pass of a layer costs twice its forward pass
(gradient of the input and of the weights), except the first convolution,
whose input is data and needs no gradient. Recomputation is never counted.
"""

from __future__ import annotations

from benchmark.reference import Shapes, pooled


def taps_3x3_same(h: int, w: int) -> int:
    """Filter taps of a 3x3 / stride-1 SAME convolution that fall on the
    image, summed over its h x w outputs: the taps on the zero padding need
    no operation and are not counted (as XLA's own cost analysis does not)."""
    return (3 * h - 2) * (3 * w - 2)


def forward_macs_per_obs(s: Shapes) -> dict:
    """Multiply-accumulates of one observation's forward pass, by layer."""
    h, w, cin = s.obs_shape
    macs = {}
    for i, ch in enumerate(s.channel_sections):
        macs[f"section{i}.conv"] = taps_3x3_same(h, w) * cin * ch
        h, w = pooled(h), pooled(w)
        macs[f"section{i}.blocks"] = (
            s.blocks_per_section * 2 * taps_3x3_same(h, w) * ch * ch
        )
        cin = ch
    macs["fc"] = h * w * cin * s.fc_size
    core = s.fc_size
    if s.lstm_size:
        macs["lstm"] = (s.fc_size + s.lstm_size) * 4 * s.lstm_size
        core = s.lstm_size
    macs["heads"] = core * (s.num_actions + s.num_values)
    return macs


def step_flops(s: Shapes, unroll_length: int, batch_size: int) -> float:
    """Model FLOPs of one learner step: the forward pass over T+1
    observations of each unroll (the last one only bootstraps the value),
    the backward pass over the T that are trained on."""
    macs = forward_macs_per_obs(s)
    fwd = 2.0 * sum(macs.values())
    bwd = 2.0 * fwd - 2.0 * macs["section0.conv"]
    return batch_size * ((unroll_length + 1) * fwd + unroll_length * bwd)


def lstm_unroll_forward(config: dict, chips: int) -> tuple:
    """(FLOPs, bytes) of one forward unroll of the LSTM core over the T+1
    observations of a batch's share of one chip.

    FLOPs: per step the two gate products, `[B, F] x [F, 4H]` and
    `[B, H] x [H, 4H]`. Bytes, float32, the least the unroll has to move
    to and from the chip's main memory: both weight matrices and the bias
    once, the features of every step in, the hidden state of every step
    out. (What an implementation saves for its backward pass is its own
    choice and is not counted; XLA keeps all of it in on-chip memory here.)
    At the benchmark's sizes the FLOPs are the larger bound."""
    m = config["model"]
    rows = int(config["batch_size"]) // chips
    steps = int(config["unroll_length"]) + 1
    feat, hid = int(m["fc_size"]), int(m["lstm_size"])
    flops = steps * 2.0 * rows * (feat + hid) * 4 * hid
    words = (feat + hid + 1) * 4 * hid + steps * rows * (feat + hid)
    return flops, 4.0 * words


OPS_AND_BYTES = {"lstm_unroll_forward": lstm_unroll_forward}
