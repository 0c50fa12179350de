"""setup_s: seconds from the first line of run.py to the first timed
call: imports, graph generation, compile or cache load, warm-up."""


def read(run):
    return run.setup_s
