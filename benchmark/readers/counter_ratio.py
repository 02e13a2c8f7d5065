"""One of the program's counters over another, both as they moved over the
window, times `args["scale"]` (100 for a share in percent): useful outcomes
over attempts, counted where the work happens.  A counter the program does
not have, or a denominator that did not move, is nothing to read."""


def read(name, args, run):
    counters = run["counters"]
    num = counters.get(args["numerator"])
    den = counters.get(args["denominator"])
    if num is None or not den:
        return None
    return float(args.get("scale", 1)) * num / den
