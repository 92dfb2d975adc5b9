"""The device's idle share (%) over the traced slice: 1 - busy / window."""


def read(obs, args):
    tr = obs.get("trace")
    if not tr or not tr.get("window_s"):
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
