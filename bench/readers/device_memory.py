"""Peak device memory of the fullest chip, in GiB."""


def read(args: dict, sources: dict):
    peak = sources["device"].get("memory_peak_bytes")
    return peak / 2 ** 30 if peak else None
