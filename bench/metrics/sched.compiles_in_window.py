"""JAX backend compiles while the window ran: each one is a program the
scheduler or the front end built for a shape set-up did not warm."""


def value(run):
    return run.compiles
