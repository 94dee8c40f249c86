"""``gpts``: grid points x time steps of useful work completed in the
window, over the window's wall time, in 10^9 per second."""


def read(record: dict):
    return record["point_steps"] / record["window_s"] / 1e9
