"""Share of the roofline of the banded-arrowhead Cholesky: the least time
the published peaks allow for the probes' useful work (counted from the
structure), over the device time of the executables that launched the
fused Cholesky kernel, the XLA work around the kernel included."""
from chipbench import counts


def read(ctx):
    return counts.roofline_share(ctx, counts.cholesky, r"band_cholesky")
