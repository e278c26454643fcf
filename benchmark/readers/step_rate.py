"""out_tok_s from step records and their end times."""
from benchmark.harness import stats


def read(run, args):
    return stats.out_tok_s(run["records"]["steps"], run["window"])
