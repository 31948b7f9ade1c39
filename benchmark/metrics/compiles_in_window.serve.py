"""Backend compiles (or cache loads) between window start and the last reply."""


def read(run):
    return float(len(run["window"]["compiles"]))
