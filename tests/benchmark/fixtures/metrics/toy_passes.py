"""A toy per-layer metric added as a file: scheduler passes in the window."""


def read(run):
    return float(len(run.facts["passes"]))
