"""``compile.backend_s``: XLA compile, or the persistent compile cache's
fetch, during set-up, from the program's compile ledger
(``compile_ledger_totals()['compile_sec']``)."""


def read(run):
    return run.counters["setup"]["compile_sec"]
