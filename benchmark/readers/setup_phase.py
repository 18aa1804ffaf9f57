"""A part of set-up, in seconds, as the load generator timed it.
args: ``phase`` (``load_s`` or ``first_read_s``)."""


def read(data, args):
    return data["setup"].get(args["phase"])
