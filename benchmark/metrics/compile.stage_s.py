"""``compile.stage_s``: jaxpr tracing and lowering during set-up, from
the program's compile ledger (``compile_ledger_totals()['stage_sec']``);
paid even when the persistent compile cache holds the program."""


def read(run):
    return run.counters["setup"]["stage_sec"]
