"""``trace_roofline_share`` where a launch reads ONE region's feed, not
the table's: the least time the chip could take for a region's rows
(HBM-bound: ``main_kernel.rows_per_launch`` rows of every input plane,
over the peak bandwidth) over the main kernel's mean time, in %.  None
without a trace, or where the traffic file gives no rows a launch."""

import trace_reduce


def read(data, args):
    kernel = data["traffic"]["main_kernel"]
    ms = trace_reduce.main_kernel_ms(data["trace"], kernel)
    if ms is None or not kernel.get("rows_per_launch"):
        return None
    least_s = trace_reduce.plan_bytes(
        kernel["rows_per_launch"], kernel["input_plane_bytes_per_row"]) / \
        data["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (ms / 1e3)
