"""setup_s: process start to the start of the lead-in."""


def read(run, args):
    return run["setup_s"]
