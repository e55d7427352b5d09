"""Operations and bytes, counted in closed form from a configuration's
widths and the traffic's real lengths: the yardstick's own arithmetic, read
from nothing the program computes. One file a reference
(`perfbench/flops/<reference>.py`) gives a model's whole step; the
functions here give a recurrent kernel call's, whatever implements it.

A multiply-add is two operations. Backward of a matrix product costs twice
its forward (one product for the input's gradient, one for the weight's).
"""

from __future__ import annotations


def recurrence_call(kind, t, b, h, gates, itemsize, backward):
    """The recurrent part of one GRU (gates=3) or LSTM (gates=4) layer call
    over [t, b] positions: h_{t-1} W for every position, W of [h, gates*h].

    Bytes are the least the recurrence has to move: forward reads the
    x-projection [t, b, gates*h] and W and writes h [t, b, h]; backward
    reads the x-projection, h, the incoming gradient [t, b, h] and W, and
    writes the x-projection's gradient and W's (float32)."""
    mm = 2.0 * t * b * h * gates * h
    x = t * b * gates * h * itemsize
    y = t * b * h * itemsize
    w = h * gates * h * itemsize
    if not backward:
        return {"kind": kind, "flops": mm, "bytes": x + w + y}
    return {"kind": kind, "flops": 2.0 * mm,
            "bytes": x + y + y + w + x + h * gates * h * 4}


def roofline_seconds(call, peaks):
    """The least time the chip could take for the call, and which bound
    applies."""
    t_flops = call["flops"] / (peaks["bf16_tflops"] * 1e12)
    t_bytes = call["bytes"] / (peaks["hbm_gbps"] * 1e9)
    return max(t_flops, t_bytes), ("compute" if t_flops >= t_bytes else "memory")
