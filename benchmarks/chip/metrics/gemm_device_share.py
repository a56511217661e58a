"""Kernels: the digit-plane GEMM kernels' share of the device's busy time
over the traced window."""
import profile_trace

# the Pallas GEMM kernels, as bw_gemm_roofline matches them
KERNEL_PATTERNS = ("bw_gemm*", "quant_gemm*")


def read(run):
    t = run.trace
    if t is None:
        return None
    kernel_s = profile_trace.matching_time(t, KERNEL_PATTERNS)
    if kernel_s <= 0.0:
        return None
    return 100.0 * kernel_s / t["busy_s"]
