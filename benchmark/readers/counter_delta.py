"""How far one of the program's counters (`lightgbm_tpu.profiling`) moved
over the window, per iteration.  A counter the program does not have is
nothing to read."""


def read(name, args, run):
    if args["counter"] not in run["counters"] or not run["iters"]:
        return None
    return run["counters"][args["counter"]] / run["iters"]
