"""Caller layer (blocksync/reactor.py `_dispatch_verify_ahead`): of the
commit verifications dispatched one height ahead, the share the next
iteration found stale, a refusal or a disconnect having taken one of
their blocks away (`blocksync_verify_ahead_total{outcome="stale"}` over
both outcomes): a launch bought and thrown away, or a walk that had
already failed. The whole window. None where nothing was dispatched
ahead or the driver hands no such counter over."""


def read(ctx):
    window = ctx["window"]
    total = window.get("verify_ahead_used", 0) + window.get("verify_ahead_stale", 0)
    return 100.0 * window["verify_ahead_stale"] / total if total else None
