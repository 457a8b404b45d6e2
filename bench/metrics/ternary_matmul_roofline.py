"""Packed serving kernel's share of its roofline: for every
``ternary_matmul`` launch in the window, the least time to read its 2-bit
weight, its f32 input and write its f32 output (``counts.packed_matmul_cost``,
shapes read from the launch itself), over the kernel's device time."""

import re

import counts

SHAPE = re.compile(r"(f32|bf16|u8)\[(\d+),(\d+)\]")


def read(ctx, summary, res):
    pk = counts.peaks(ctx.device_kind)
    roof, time = 0.0, 0.0
    for name, t in summary.op_s.items():
        if not re.match(r"^%?ternary_matmul(\.\d+)? ", name):
            continue
        shapes = [(d, int(a), int(b)) for d, a, b in SHAPE.findall(name)]
        out = shapes[0]
        x = next(s for s in shapes[1:] if s[0] != "u8" and s[1:] != (1, 1))
        m, k, n = out[1], x[2], out[2]
        roof += summary.op_n[name] * counts.roof_seconds(*counts.packed_matmul_cost(m, k, n), pk)
        time += t
    return 100.0 * roof / time if time else None
