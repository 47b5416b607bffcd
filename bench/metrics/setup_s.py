"""Process start to the first timed request or step: weights made on the
device, programs compiled or loaded from the cache, shapes warmed."""


def read(data):
    return float(data.setup_s)
