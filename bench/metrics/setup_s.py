"""setup_s: seconds from the process's start to the start of the window:
imports, making the stripes, compiling or loading the programs, warm-up."""


def read(run):
    return run.setup_s
