"""Rows the batches of the window carried, over the rows they could have
carried: the program's counters ``rows_batched`` and ``rows_fillable``
(at each batch, the ready queue's depth up to the largest batch choice),
summed over every batch (%). A plan that batches fewer queries than are
waiting reads low here while ``batch_fill`` reads full."""
from chipbench import program_spans


def read(ctx):
    rec = program_spans.recorder()
    if rec is None or not rec.counters.get("rows_fillable"):
        return None
    return 100.0 * rec.counters["rows_batched"] / rec.counters["rows_fillable"]
