"""A histogram kernel's share of its roofline, in percent, bound by
operations.

Least time = the operations the kernel's contractions perform / the device's
peak rate for the operand type; the share is that over the kernel's device
time (the value of the metric named in `args["kernel_time"]`, from the same
trace), as `hist_bytes_roofline` does for bytes.  The operations are the
program's own count (`args["ops_counter"]`): for every launch, 2 x the rows
it streams x its value rows x its store columns x its bins, each as the
kernel pads it, summed over the devices — so the count is what the MXU was
asked to do, padding and empty leaf slots included, and the share cannot
pass 100 %.  The peak is the int8 rate where the histogram operands are one
byte wide and the bf16 rate otherwise (`harness/peaks.json`).
"""


def read(name, args, run):
    owned = run.get("trace_op_time")
    ops = run["counters"].get(args["ops_counter"])
    if not owned or not ops or not owned.get(args["kernel_time"]):
        return None
    n_dev = max(len(run["trace"].devices), 1)
    peak = run["peaks"]["int8_ops_per_s" if run["store"]["hist_itemsize"] == 1
                        else "bf16_flops_per_s"]
    least_s = ops / n_dev / peak
    return 100.0 * least_s / (owned[args["kernel_time"]] / 1e9)
