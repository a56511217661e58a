"""Kernels: the digit-plane GEMM kernels' share of their roofline.

For the traced steps, the least time of every quantized GEMM of a step
(the larger of 2 M K N over the int8 peak and the bytes of the logical
int8 GEMM over the HBM bandwidth, each GEMM on its own, M = the batch's
slots) divided by the device time of the GEMM kernels.  The work is
counted from shapes by the configuration's work module, ``run.work``, the
same whatever implements a GEMM."""
import profile_trace

# the Pallas GEMM kernels: in the device trace each is a custom call
# named after the function that wraps its pallas_call
KERNEL_PATTERNS = ("bw_gemm*", "quant_gemm*")


def read(run):
    t = run.trace
    if t is None or not t["steps"]:
        return None
    kernel_s = profile_trace.matching_time(t, KERNEL_PATTERNS)
    if kernel_s <= 0.0:
        return None
    least = t["steps"] * run.work.step_gemm_least_time(
        run.model, run.serve["batch"], run.peaks, run.bits)
    return 100.0 * least / kernel_s
