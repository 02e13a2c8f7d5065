"""A time the benchmark took with the host's clock (`time.perf_counter`):
`args["key"]` names it among the job's `clock` facts."""


def read(name, args, run):
    return run["clock"].get(args["key"])
