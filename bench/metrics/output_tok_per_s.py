"""Output tokens of the requests completed in the window over the window."""


def read(data):
    done = data.sut.completed()
    return sum(r["n_out"] for r in done) / data.window_s if done else None
