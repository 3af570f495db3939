"""Share of the traced resume window in which no operation ran on the
device: 1 - (union of device-operation intervals) / window."""


def read(obs):
    tr = obs.get("trace")
    if not tr or not tr.get("window_s") or "restore_s" not in obs:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
