"""Seconds per round: the window's completed rounds over their number."""


def read(run):
    return run.round_s
