"""The flash attention kernels against their roofline: for every
execution of a Mosaic kernel in the traced interval that takes
[sequences, heads, positions, head_dim] operands, the least time the
chip could take for that call (the larger of operations over the bf16
peak and bytes over the bandwidth peak, from counts.flash_*; at 4096
positions and head size 128 the operations bound every one of them, and
the line printed says so) over the device time the kernels took.

The trace names a kernel by its HLO instruction (`%closed_call.6`,
`%checkpoint.22`: nothing stable), so a kernel is known by its call:
`custom_call_target="tpu_custom_call"` with three operands is the
forward (q, k, v), with six the backward (q, k, v, do, lse, delta), of
which the one with a single result is the dQ kernel and the one with
two the dK/dV kernel.  Shapes are read from the operands, so the count
is the chip's own share under `shard_map`.
"""
import re

import counts
import trace_reduce as TR

KERNEL = re.compile(r'custom-call\((.*)\), custom_call_target="tpu_custom_call"')
SHAPE = re.compile(r"bf16\[(\d+),(\d+),(\d+),(\d+)\]")


def read(run):
    tr = run["trace"]
    if tr is None:
        return None
    t0, t1 = run["window"]
    least = spent = 0.0
    seen = {"forward": 0, "backward dq": 0, "backward dkv": 0}
    bounds = set()
    for name, s, d in TR.op_events(TR.first_device(tr)):
        if s < t0 or s + d > t1:
            continue
        m = KERNEL.search(name)
        if not m:
            continue
        operands = m.group(1).count("%")
        shape = SHAPE.search(m.group(1))
        if shape is None or operands not in (3, 6):
            continue
        b, h, p, hd = (int(x) for x in shape.groups())
        args = (h, hd, b, p)
        if operands == 3:
            kind, flops, nbytes = ("forward", counts.flash_fwd_flops(*args),
                                   counts.flash_fwd_bytes(*args))
        elif name.split(" = ", 1)[1].lstrip().startswith("("):
            kind, flops, nbytes = ("backward dkv",
                                   counts.flash_bwd_dkv_flops(*args),
                                   counts.flash_bwd_bytes(*args))
        else:
            kind, flops, nbytes = ("backward dq",
                                   counts.flash_bwd_dq_flops(*args),
                                   counts.flash_bwd_bytes(*args))
        t, bound = counts.least_time(flops, nbytes, run["peaks"])
        bounds.add(bound)
        seen[kind] += 1
        least += t
        spent += d / 1e9
    if not spent:
        return None
    print(f"FLASH kernels in the traced interval: {seen}; bound by "
          f"{sorted(bounds)}; least {least * 1e3:.2f} ms of "
          f"{spent * 1e3:.2f} ms spent", flush=True)
    return 100.0 * least / spent
