"""``recv_unpack``'s share of its roofline (%): the bytes the receive unpack
must move (costs.ep_bytes) over the HBM peak, over the kernel's summed
device time, per round trip."""
from metrics._lib import kernel_roofline


def read(ctx):
    return kernel_roofline(ctx, "recv_unpack")
