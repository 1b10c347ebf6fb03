"""Host prep and transfer (ops/verify.py `PubkeyCache`): rows of the
slice's cached launches whose public key's table was already on the
device, over the rows looked up: `engine_pk_cache_rows_total` less
`engine_pk_cache_missed_rows_total`. A miss pays a table build, a
launch of its own, before the batch's. None where nothing was looked
up, or the program has no such counters."""

from benchmark.readers import ENGINE, counter_delta


def read(ctx):
    rows = counter_delta(ctx, ENGINE + "pk_cache_rows_total")
    missed = counter_delta(ctx, ENGINE + "pk_cache_missed_rows_total")
    return 100.0 * (rows - missed) / rows if rows else None
