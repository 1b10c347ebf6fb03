"""Host prep and transfer (ops/verify.py `PubkeyCache.ensure_snapshot`):
milliseconds of pubkey-cache fills, table build and publish, wall, per
block applied, over the **whole window**
(`engine_pk_cache_fill_seconds_total`'s growth, which the driver hands
over). A fill runs on the engine's dispatch thread before the launch
of the batch that missed. None where the driver hands no such counter
over, as on a program without it."""


def read(ctx):
    window = ctx["window"]
    if "pk_fill_s" not in window or not window["ops"]:
        return None
    return 1e3 * window["pk_fill_s"] / window["ops"]
