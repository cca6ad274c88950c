"""scan_compact_roofline.query: kernel_bytes.scan_compact at the HBM peak
over the in-window time of the jit_scan_compact modules (%).

The counter is (4 W + 1) n a call: the W uint32 word rows and the mask
the compaction must read, fixed by the data.  The program counts it only
where ``jit_scan_compact`` runs as its own program (the eager device
scan), never where a fused span program inlines the kernel.  The kernel's
jit name is part of this metric's definition: a change that renames the
kernel takes the metric with it."""

from lib.span_readers import kernel_roofline


def read(ctx):
    return kernel_roofline(ctx, "scan_compact")
