"""Host seconds of the program's `build_tables` (neighbour and minimal
routing-record tables), around the harness's call."""


def read(run):
    return run.tables_s
