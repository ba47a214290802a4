"""Share of the device's busy time spent in Mosaic (Pallas) custom calls,
from the device trace. A roofline share per kernel needs kernel names the
trace does not carry yet."""


def read(obs):
    tr = obs.get("trace")
    if not tr or obs.get("kind") != "fit_cycle":
        return None
    return 100.0 * tr["by_category_s"].get("mosaic", 0.0) / tr["busy_s"]
