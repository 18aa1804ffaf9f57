"""Mean device duration, in ms, of the operations (``"of": "ops"``, the
``XLA Ops`` line) or programs (``"of": "modules"``, the ``XLA Modules``
line) whose name holds any of ``match``, over every device plane and
every occurrence in the traced window: on a mesh, the mean per shard.
args: ``of``, ``match``.  None without a trace, or where nothing of
that name ran."""

import trace_reduce


def read(data, args):
    return trace_reduce.main_kernel_ms(data["trace"], args)
