"""The ``grad_mag`` kernel's share of its roofline, in %.

Measured time: the device time of the jitted ``kops.grad_mag`` program
(its transposes and shifted copies included).  Least time: the larger of
its operations over the chip's peak FLOP/s and its bytes over the peak HBM
bandwidth, with the bytes that the algorithm needs: the float32 stack and
the boolean valid mask read once, the two float32 sums written once."""

from chipbench import roofline

PROGRAM = "jit_grad_mag"


def cost(depth: int, px: int, bands: int, itemsize: int = 4):
    """(operations, bytes) of one call on a [depth, px, px, bands] stack."""
    pixels = px * px
    # per band: two differences, masked, squared and summed; per pixel:
    # the masks, the root, the weighted sum and the count
    flops = 8 * depth * pixels * bands + 8 * depth * pixels
    nbytes = (itemsize * depth * pixels * bands   # images
              + depth * pixels                    # valid, bool
              + 2 * 4 * pixels)                   # grad_sum and count
    return flops, nbytes


def read(run):
    c = run.config
    return roofline.share(run, PROGRAM, *cost(c["depth"], c["tile_px"],
                                              c["bands"]))
