"""Seconds of set-up spent inside the program's spans named in ``spans``
(``compile:trace``, ``compile:lower``, ``compile:executable``): the
summed length of those that ended before the window opened, nested ones
counted once.  ``None`` where the ring holds none (a program that does
not record them) or has evicted any record."""
from benchmark.lib.reducers import program_spans


def reduce(ctx, spans):
    found, win = program_spans.ring(), program_spans.window_of(ctx)
    if found is None or win is None:
        return None
    records, evicted = found
    mine = [r for r in records
            if r["name"] in spans and program_spans.end(r) <= win[0]]
    if evicted and mine:
        program_spans.log("the ring evicted %d records: set-up not reduced"
                          % evicted)
    if evicted or not mine:
        return None
    return program_spans.union_seconds(mine)
