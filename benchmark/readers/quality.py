"""A quality figure the benchmark computed on the configuration's test split with its own
numpy code (`harness/walk.py`): `args["key"]` names it."""


def read(name, args, run):
    return run["quality"].get(args["key"])
