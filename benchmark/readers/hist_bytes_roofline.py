"""A histogram kernel's share of its roofline, in percent, bound by bytes.

Least time = bytes the passes must read / the device's peak HBM bandwidth;
the share is that over the kernel's device time (the value of the metric
named in `args["kernel_time"]`, from the same trace).  Bytes: every row the
passes touched (`args["rows_counter"]`, the program's count) is read once at
the width the device keeps it — `histogram_row_bytes` below.  The output
histograms are thousands of times smaller and are left out.

The one-hot formulation also spends MXU operations (rows x bins x slots per
pass); no counter gives the slots per pass yet, so the share by operations
is not computed and this one is stated as bound by bytes.
"""


def histogram_row_bytes(columns: int, bin_itemsize: int,
                        hist_itemsize: int) -> int:
    """Bytes one histogram pass must read for one row: its bin in every
    store column, gradient and hessian and the row's weight in the
    histogram's operand type, and the int32 leaf slot the row belongs to."""
    return columns * bin_itemsize + 3 * hist_itemsize + 4


def read(name, args, run):
    owned = run.get("trace_op_time")
    rows = run["counters"].get(args["rows_counter"])
    if not owned or not rows or not owned.get(args["kernel_time"]):
        return None
    n_dev = max(len(run["trace"].devices), 1)
    st = run["store"]
    least_s = (rows / n_dev
               * histogram_row_bytes(st["columns"], st["bin_itemsize"],
                                     st["hist_itemsize"])
               / run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / (owned[args["kernel_time"]] / 1e9)
