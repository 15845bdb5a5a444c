"""Process start to window open: imports, weights from the seed,
compilation or cache loads, warm-up, and filling the slots."""


def read(rec):
    return rec.setup_s
