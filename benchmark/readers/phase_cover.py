"""Share of one row of ``/health`` ``tracing.phases`` that other rows
cover over the window: 100 · Σ Δwall_ms(part) / Δwall_ms(whole), each rise
taken as ``counter_delta`` takes it (when the window's last read had
returned minus at ``go``).  args: ``whole``, a row's name; ``parts``, a
list of rows' names, DISJOINT in time on the thread that runs ``whole``
(a row that keeps its self time, or a leaf).  A part that a sample does
not hold adds 0: a program from before the row existed reads the share
its older rows cover.  None where ``whole`` is absent or did not rise."""


def wall_ms(sample, row):
    phases = sample.get("health", {}).get("tracing", {}).get("phases", {})
    got = phases.get(row)
    return got.get("wall_ms") if isinstance(got, dict) else None


def rise(data, row):
    go = wall_ms(data["counters_go"], row)
    end = wall_ms(data["counters_end"], row)
    return None if go is None or end is None else end - go


def read(data, args):
    whole = rise(data, args["whole"])
    if whole is None or whole <= 0:
        return None
    return 100.0 * sum(rise(data, part) or 0.0
                       for part in args["parts"]) / whole
