"""Process start to the window's opening: imports, device state, warm-up
(with compilation where the cache misses) and inputs made ahead."""


def value(run):
    return run.setup_s
