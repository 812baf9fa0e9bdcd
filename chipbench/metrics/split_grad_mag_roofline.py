"""The split ``grad_mag`` program's share of its roofline, in %.

Measured time: the device time of the jitted ``split_grad_mag`` program
(its halo exchange, transposes, shifted copies, threshold and closing
included), summed over the chips: one call per chip a tile.  Least time
of a call: the larger of one shard's operations over the chip's peak
FLOP/s and its bytes over the peak HBM bandwidth, with the bytes that the
algorithm needs: the float32 shard with its halo row and column and the
boolean mask shard with its halo, read once; the two float32 sums and the
boolean field map, written once."""

from chipbench import roofline

PROGRAM = "jit_split_grad_mag"


def cost(depth: int, px: int, bands: int, mesh, itemsize: int = 4):
    """(operations, bytes) of one shard's call on a [depth, px, px, bands]
    stack split over a (rows, cols) mesh."""
    rows, cols = mesh
    h, w = px // rows, px // cols
    pixels = h * w
    read_px = pixels + h + w  # the shard, its east column and south row
    # per band: two differences, masked, squared and summed; per pixel:
    # the masks, the root, the weighted sum and the count
    flops = 8 * depth * pixels * bands + 8 * depth * pixels
    nbytes = (itemsize * depth * read_px * bands   # images
              + depth * read_px                    # valid, bool
              + 2 * 4 * pixels                     # grad_sum and count
              + pixels)                            # fields, bool
    return flops, nbytes


def read(run):
    c = run.config
    return roofline.share(run, PROGRAM, *cost(c["depth"], c["tile_px"],
                                              c["bands"], c["mesh"]))
