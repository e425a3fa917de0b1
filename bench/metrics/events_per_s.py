"""Events committed inside the window, over the window (host clock)."""


def read(rec):
    return rec.committed / rec.seconds
