"""out_tok_s from the program's step records: their own count of
tokens handed to requests, and their end times."""
from benchmark.harness import stats


def read(run, args):
    return stats.out_tok_s(run["records"]["steps"], run["window"])
