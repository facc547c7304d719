"""Tokens of every step completed in the window over the whole window
(host clock; all chips of the cell together)."""


def read(run):
    return run["tokens"] / run["window_s"]
