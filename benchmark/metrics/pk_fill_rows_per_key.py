"""Host prep and transfer (ops/verify.py `PubkeyCache.ensure_snapshot`):
rows a pubkey-cache fill's two programs ran at per key they built a
table for, over the **whole window** (the growth of
`engine_pk_cache_fill_rows_total` over that of
`engine_pk_cache_filled_keys_total`, which the driver hands over). A
fill runs at the launch bucket of the batch that missed, whatever the
number of misses: 1024 rows for one new key of a 1000-validator
commit. None where nothing was filled or the driver hands no such
counter over."""


def read(ctx):
    window = ctx["window"]
    keys = window.get("pk_filled_keys")
    return window["pk_fill_rows"] / keys if keys else None
