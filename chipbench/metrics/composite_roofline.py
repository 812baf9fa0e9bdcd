"""The composite kernel's share of its roofline, in %.

Measured time: the device time of the jitted ``kops.composite`` program
(its bands-major transposes included).  Least time: the larger of its
operations over the chip's peak FLOP/s and its bytes over the peak HBM
bandwidth, with the bytes that the algorithm needs: the stack and the
weights read once, the composite written once."""

from chipbench import roofline

PROGRAM = "jit_composite"


def cost(depth: int, px: int, bands: int, itemsize: int = 4):
    """(operations, bytes) of one call on a [depth, px, px, bands] stack."""
    pixels = px * px
    flops = (2 * depth * pixels * bands   # weighted sum
             + depth * pixels              # weight sum
             + 2 * pixels * bands)         # eps and division
    nbytes = itemsize * (depth * pixels * bands   # images
                         + depth * pixels          # weights
                         + pixels * bands)         # composite
    return flops, nbytes


def read(run):
    c = run.config
    return roofline.share(run, PROGRAM, *cost(c["depth"], c["tile_px"],
                                              c["bands"]))
