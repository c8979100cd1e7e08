"""Share of the roofline of the banded-arrowhead Cholesky where the band is
streamed from HBM: the least time the published peaks allow for the
probes' useful work (counted from the structure), over the device time of
the executables that launched the streamed Cholesky kernel, the XLA work
around the kernel included."""
from chipbench import counts


def read(ctx):
    return counts.roofline_share(ctx, counts.cholesky,
                                 r"band_cholesky_stream")
