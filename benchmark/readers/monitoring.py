"""Compilation as `jax.monitoring` reported it: `args["when"]` is
`before_window` or `in_window`, `args["key"]` one of `programs` (compiled or
read from the persistent cache), `seconds`, `hits`, `misses`."""


def read(name, args, run):
    return run["compile"][args["when"]][args["key"]]
