"""Seconds from process start to the first timed step: interpreter
and imports, weights, the job's first steps and every compile."""


def read(run):
    return run["setup_s"]
