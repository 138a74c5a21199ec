"""Set-up: seconds from the start of the process to the end of the warm-up
solve (generation, layouts, placement, compilation, warm-up)."""


def read(run):
    return run.setup_s
