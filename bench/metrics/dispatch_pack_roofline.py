"""``dispatch_pack``'s share of its roofline (%): the bytes the send pack
must move (costs.ep_bytes) over the HBM peak, over the kernel's summed
device time, per round trip."""
from metrics._lib import kernel_roofline


def read(ctx):
    return kernel_roofline(ctx, "dispatch_pack")
