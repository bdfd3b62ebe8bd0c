"""New entries of A's persistent compile cache between the window's
start and end (A writes every compile there, whatever it took): 0 once a
cell has run in a checkout. One entry per cell, `<cell>_compiles_in_window`."""


def read(run):
    new = run.cache1 - run.cache0
    return float(len([n for n in new if not n.endswith("-atime")]))
