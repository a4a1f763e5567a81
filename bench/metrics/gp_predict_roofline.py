"""GP kernels: the fused batched predict's (`gp_predict_experts`) share of
its roofline in the traced window.  The least time each launch could
take, from its shapes (`bench/harness/roofline.py`) and the chip's peaks,
summed over the window's launches, over the kernel's time in the device
trace.  Nothing is read when the trace holds no such kernel."""
from bench.harness import roofline


def read(run):
    t = run.trace_result
    if t is None:
        return None
    kernel_s = t.kernel_s(roofline.GP_PREDICT_KERNEL)
    if kernel_s <= 0:
        return None
    least = roofline.least_seconds_gp_predict(run.launches, run.peaks,
                                             run.device_kind)
    return 100.0 * least / kernel_s
