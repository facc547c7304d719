"""``peak_bytes_in_use`` after the window, on the fullest device, in
GiB (set-up and window both count; the reference runs after)."""


def read(run):
    return run["memory_peak_bytes"] / 2 ** 30
