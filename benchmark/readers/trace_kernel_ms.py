"""Mean device duration of the cell's main kernel, whose operation
names the traffic file gives under ``main_kernel``."""

import trace_reduce


def read(data, args):
    return trace_reduce.main_kernel_ms(data["trace"],
                                       data["traffic"]["main_kernel"])
