"""The median duration of one of the driver's spans over the window."""
import statistics


def read(obs, args):
    d = obs["spans"].get(args["span"])
    if not d:
        return None
    return statistics.median(d) * args.get("scale", 1.0)
