"""1 - union of device op intervals / traced window, in percent."""


def read(run, args):
    t = run.get("trace")
    if not t or not t.get("window_s"):
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
