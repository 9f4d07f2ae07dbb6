"""The three Pallas flash-attention kernels in a device trace.

The `pallas_call`s carry no `name=` yet, so a kernel is known by what it
is: a custom call with target `tpu_custom_call` over [batch*heads, S, d]
tensors, told apart by its operands and outputs (read from a real trace,
PR 24): forward takes q, k, v and gives (o, lse); dq takes six and gives
one tensor; dk/dv takes six and gives two.
"""

import re

from lib import flops, peaks, trace

_SHAPE = re.compile(r"(bf16|f16|f32)\[(\d+),(\d+),(\d+)\]")
_ITEMSIZE = {"bf16": 2, "f16": 2, "f32": 4}


def classify(name):
    """(kernel, batch_heads, seq_len, head_dim, itemsize) or None."""
    p = trace.parse(name)
    if p is None or p[2] != "custom-call" or p[4] != "tpu_custom_call":
        return None
    _, shapes, _, operands, _ = p
    outs = _SHAPE.findall(shapes)
    if not outs:
        return None
    dtype, bh, s, d = outs[0]
    if operands == 3 and len(outs) == 2:
        kernel = "forward"
    elif operands == 6 and len(outs) == 1:
        kernel = "dq"
    elif operands == 6 and len(outs) == 2:
        kernel = "dkv"
    else:
        return None
    return kernel, int(bh), int(s), int(d), _ITEMSIZE[dtype]


def kernel_events(run):
    """[(kernel, shape..., duration_ns)] over all devices, or []."""
    if not run.trace:
        return []
    out = []
    for name, dur in trace.matching(
            run.trace, lambda n: classify(n) is not None):
        out.append((*classify(name), dur))
    return out


def least_seconds(run, events):
    """Sum of each call's roofline time on this device, and how the
    calls split between the two roofs."""
    p = peaks.peaks(run.device["kind"])
    total = 0.0
    roofs = {}
    for kernel, bh, s, d, itemsize, _ in events:
        seconds, roof = flops.roofline_seconds(
            flops.causal_attention_kernel_flops(bh, s, d, kernel),
            flops.attention_kernel_bytes(bh, s, d, kernel, itemsize),
            p["flops_bf16"], p["hbm_bytes_per_s"])
        total += seconds
        roofs[roof] = roofs.get(roof, 0) + 1
    return total, roofs
