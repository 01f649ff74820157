"""Bytes the fused batch-norm epilogue calls of one ResNet training step
*need*, from the shapes alone.

``bn_act_fwd`` computes y = act(x*a + b [+ z]) per channel: it must read x
(and the residual z where the block's add is folded in) and write y.
``bn_act_bwd`` computes dx (and, with a residual, the gradient g that flows
to the shortcut) from y, dy and x: three reads and one or two writes.  The
per-channel vectors are a few kilobytes and are left out.  No arithmetic
worth the name: both kernels are memory-bound, so the roofline is bytes over
the chip's memory bandwidth.

Sites of a bottleneck ResNet (stages of ``counts`` blocks, widths 64..512,
the down-sampling stride on the 3x3): the stem, and per block the norm after
the 1x1 (at the block's input resolution), after the 3x3, and after the last
1x1 together with the residual add — 1 + 3*16 = 49 at depth 50.
"""
from __future__ import annotations

BOTTLENECK_COUNTS = {50: (3, 4, 6, 3), 101: (3, 4, 23, 3), 152: (3, 8, 36, 3)}


def sites(depth: int, image: int):
    """``(elements per image, has_residual)`` for every bn+act site."""
    hw = image // 2                       # stem: 7x7 stride 2
    out = [(64 * hw * hw, False)]
    hw //= 2                              # 3x3 max-pool stride 2
    for stage, count in enumerate(BOTTLENECK_COUNTS[depth]):
        width = 64 * 2 ** stage
        for i in range(count):
            stride = 2 if i == 0 and stage != 0 else 1
            out.append((width * hw * hw, False))          # after the 1x1
            hw //= stride
            out.append((width * hw * hw, False))          # after the 3x3
            out.append((4 * width * hw * hw, True))       # last 1x1 + add
    return out


def needed_bytes_per_step(depth: int, image: int, per_chip_batch: int,
                          itemsize: int) -> dict:
    """Bytes per device and step, and calls per step, of each kernel."""
    fwd = bwd = 0
    found = sites(depth, image)
    for elements, residual in found:
        n = elements * per_chip_batch * itemsize
        fwd += n * (3 if residual else 2)
        bwd += n * (5 if residual else 4)
    return {"bn_act_fwd": {"bytes": fwd, "calls": len(found)},
            "bn_act_bwd": {"bytes": bwd, "calls": len(found)}}
