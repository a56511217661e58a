"""Expert layer: the grouped expert GEMM kernel's (``bw_gemm_experts``)
share of the device's busy time over the traced window.  A program
without the kernel reads nothing."""
import profile_trace

KERNEL_PATTERNS = ("bw_gemm_experts*",)


def read(run):
    t = run.trace
    if t is None:
        return None
    kernel_s = profile_trace.matching_time(t, KERNEL_PATTERNS)
    if kernel_s <= 0.0:
        return None
    return 100.0 * kernel_s / t["busy_s"]
