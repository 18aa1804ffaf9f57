"""The least time the chip could take for the plan (HBM-bound: the
bytes of its input planes over the peak bandwidth) over the main
kernel's mean time, in %."""

import trace_reduce


def read(data, args):
    kernel = data["traffic"]["main_kernel"]
    ms = trace_reduce.main_kernel_ms(data["trace"], kernel)
    if ms is None:
        return None
    least_s = trace_reduce.plan_bytes(
        data["rows"], kernel["input_plane_bytes_per_row"]) / \
        data["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (ms / 1e3)
