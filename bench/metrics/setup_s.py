"""Process start to window start: imports, the table and the events,
the engine, and the warm pass that compiles or loads every program
(host clock)."""


def read(rec):
    return rec.setup_s
