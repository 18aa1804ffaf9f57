"""A counter of the status server when the window's last read had
returned minus at ``go``.  args: ``counter``, a dotted path into
{"health": /health, "flight_recorder": /debug/trace's block}; with
``"reads_per": true`` the reads served in that span over the rise."""


def dig(obj, path):
    for key in path.split("."):
        obj = obj[key]
    return obj


def read(data, args):
    rise = dig(data["counters_end"], args["counter"]) - \
        dig(data["counters_go"], args["counter"])
    if not args.get("reads_per"):
        return rise
    return len(data["reads"]) / rise if rise > 0 else None
