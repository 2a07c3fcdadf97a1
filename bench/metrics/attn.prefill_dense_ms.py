"""Device time of the dense layers' streamed prefill attention per
prefill program execution, in ms: the ``paged_prefill_dense`` kernel's
summed time in the traced slice over the executions of the prefill
module there. None when the slice holds neither (an untraced run, a
slice without a prefill wave, a program without the kernel)."""

KERNEL = "paged_prefill_dense"
#: the jitted chunked prefill, as its module is named in a device trace
PREFILL_MODULE = "jit_prefill"


def read(run):
    if run.trace is None:
        return None
    kernel_ns = run.trace.kernel_ns(KERNEL)
    runs = run.trace.module_times(PREFILL_MODULE)
    if not kernel_ns or not runs:
        return None
    return kernel_ns / len(runs) * 1e-6
