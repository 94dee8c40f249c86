"""``setup_s``: process start to the first timed call: JAX and chip
start-up, the state made on the device from the seed, ``api.compile``,
the compile of the timed programs (from the persistent cache after the
first run) and the warm-up calls."""


def read(record: dict):
    return record["setup_s"]
