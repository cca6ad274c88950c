"""fixed64_pairs_roofline.read: kernel_bytes.fixed64_pairs at the HBM peak
over the in-window time of the jit_fixed64_pairs modules (%).

The kernel's jit name, ``jit_fixed64_pairs``, is part of this metric's
definition: a change that renames the kernel takes the metric with it."""

from lib.span_readers import kernel_roofline


def read(ctx):
    return kernel_roofline(ctx, "fixed64_pairs")
