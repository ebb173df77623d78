"""nsa_tables_s: host seconds per sweep building NSA's bucket tables
(``ops._nsa_tables`` over every scenario row), averaged over the window's
sweeps — the program's ``nsa.tables`` span."""

from benchlib import spans


def read(run):
    return spans.per_sweep_s(run, "nsa.tables")
