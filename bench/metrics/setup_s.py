"""Set-up: process start to the first timed operation (deployment drawn,
programs loaded or compiled, every shape of the cell warmed up)."""


def read(run):
    return run.setup_s
