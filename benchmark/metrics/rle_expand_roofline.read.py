"""rle_expand_roofline.read: kernel_bytes.rle_expand at the HBM peak over
the in-window time of the jit_rle_expand modules (%).

The kernel's jit name, ``jit_rle_expand``, is part of this metric's
definition: a change that renames the kernel takes the metric with it."""

from lib.span_readers import kernel_roofline


def read(ctx):
    return kernel_roofline(ctx, "rle_expand")
