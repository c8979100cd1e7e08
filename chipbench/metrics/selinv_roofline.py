"""Share of the roofline of the selected inversion: the least time the
published peaks allow for its useful work (counted from the structure),
over the device time of the executables that launched the fused
selected-inversion kernel, the XLA work around the kernel included."""
from chipbench import counts


def read(ctx):
    return counts.roofline_share(ctx, counts.selinv, r"selinv_sweep")
