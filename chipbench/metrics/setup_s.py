"""Seconds from the start of the process to the opening of the window:
imports, weights from the seed, compilation (or the compile cache's
load), warm-up, and for training the first steps the check reads."""


def read(run):
    return run["setup_s"]
