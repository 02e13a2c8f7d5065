"""Peak device memory of the fullest device as the window closed
(`harness/device.peak_bytes`: buffers plus what the loaded programs reserve),
in units of `args["per"]` bytes."""


def read(name, args, run):
    return run["memory"]["peak_bytes"] / float(args.get("per", 1))
