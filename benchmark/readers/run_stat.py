"""A number the load generator reports about its own run.  args:
``stat``."""


def read(data, args):
    return data["stats"].get(args["stat"])
