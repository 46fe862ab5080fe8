"""Device (TPU v5e): the share of the traced window, from the first job's
start to the last one's end, in which no operation ran on the device,
averaged over the cell's chips: a chip that ran no operation is idle
through the whole window."""


def read(run: dict):
    trace = run["trace"]
    if trace is None or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
