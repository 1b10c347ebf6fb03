"""`decode_ms_per_update.light` and `light_rows_direct_share.light` on
hand-made slices: the arithmetic only."""

import pytest

from benchmark.metrics import decode_ms_per_update, light_rows_direct_share


def span(name, t0, t1, ends=True, **args):
    return {"name": name, "cat": "x", "t0": t0 * 1e6, "t1": t1 * 1e6, "tid": 1,
            "ends_in_slice": ends, "args": args}


def update(t0, parts, ends=True):
    """One update of 20 ms that fetched a block and read `parts` of it:
    (milliseconds, args of the span) each, one after another."""
    out, at = [span("light.update", t0, t0 + 20, ends), span("light.fetch", t0, t0 + 12, ends, purpose="target")], t0 + 1
    for ms, args in parts:
        out.append(span("light.decode_part", at, at + ms, ends, **args))
        at += ms
    return out


DIRECT = [(2, {"part": "commit", "path": "direct", "rows": 1000}), (1, {"part": "validator_set", "path": "direct", "rows": 1000})]
MESSAGE = [(4, {"part": "commit", "path": "message", "rows": 150}), (3, {"part": "validator_set", "path": "message", "rows": 150})]
PARENT = [(4, {"part": "commit"}), (4, {"part": "validator_set"})]  # a program whose span says no path


@pytest.mark.parametrize("spans,decode_ms,direct_share", [
    (update(0, DIRECT) + update(20, DIRECT), 3.0, 100.0),
    (update(0, MESSAGE), 7.0, 0.0),
    (update(0, DIRECT) + update(20, MESSAGE), 5.0, 100.0 * 2000 / 2300),
    (update(0, PARENT) + update(20, PARENT), 8.0, None),
    # a catch-up update that fetched two blocks: both blocks' parts over the one update
    (update(0, DIRECT + DIRECT), 6.0, 100.0),
    # the update open when the slice ends: its time inside the slice counts, as `fetch_ms_per_update.light`
    # counts it, over the updates that ended; its rows, not yet said, do not
    (update(0, DIRECT) + update(20, DIRECT[:1], ends=False), 5.0, 100.0),
    (update(0, DIRECT, ends=False), None, None),
    # a part the message did not carry builds no row
    (update(0, [(0.5, {"part": "commit", "path": "message", "rows": 0})]), 0.5, None),
    ([span("light.update", 0, 20)], None, None),  # blocks that defer nothing
    ([], None, None),
])
def test_decode_time_and_direct_share(spans, decode_ms, direct_share):
    ctx = {"spans": spans}
    for got, want in ((decode_ms_per_update.read(ctx), decode_ms), (light_rows_direct_share.read(ctx), direct_share)):
        assert got is None if want is None else got == pytest.approx(want)
