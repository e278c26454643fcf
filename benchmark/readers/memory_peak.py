"""Peak bytes in use on the fullest chip, in GB (1e9)."""


def read(run, args):
    peak = run["device"].get("memory_peak_bytes")
    return peak / 1e9 if peak else None
