"""fused_gate_up + grouped_gemm: least time over device time (%)."""
import layer


def read(run):
    return layer.roofline(run, layer.EXPERT_KERNELS, layer.expert_cost)
