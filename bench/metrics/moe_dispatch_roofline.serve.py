"""router_topk + permute + unpermute: least time over device time (%)."""
import layer


def read(run):
    return layer.roofline(run, layer.DISPATCH_KERNELS, layer.dispatch_cost)
