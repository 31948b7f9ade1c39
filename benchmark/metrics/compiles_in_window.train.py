"""Backend compiles (or cache loads) that ended inside the measured train() call."""


def read(run):
    return float(len(run["window"]["compiles"]))
