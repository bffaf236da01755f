"""The device's idle share over the profiled slice of the window."""


def read(args: dict, sources: dict):
    profile = sources["profile"]
    if not profile or not profile["devices"]:
        return None
    return 100.0 * (1.0 - profile["busy_s"] / profile["window_s"])
