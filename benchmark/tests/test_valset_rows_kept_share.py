"""`valset_rows_kept_share.sync` on hand-made windows, the arithmetic,
and in a traced rehearsal of `blocksync-1k-churn`. The window's
`state.save` spans as `window_spans.window` hands them over; a span
without `rows` is a program that does not count them."""

import pytest
from conftest import run_cell
from test_blocksync_churn import CELL, churn_root, rehearsal_settings  # noqa: F401 - fixtures

from benchmark.metrics import valset_rows_kept_share


def save(t0, height, cpu=True, **args):
    args["height"] = height
    if cpu:
        args["cpu_us"] = 1000.0
    return {"name": "state.save", "cat": "state", "tid": 1, "t0": t0 * 1e6, "t1": (t0 + 4) * 1e6,
            "args": args}


def ctx_of(spans):
    return {"window_spans": {"spans": spans, "tnames": {1: "reactor"}}}


@pytest.mark.parametrize("spans,share", [
    # a block that brought a key (one row written anew) and one that did not
    ([save(0, 7, rows=4004, rows_kept=4003), save(10, 8, rows=3003, rows_kept=3003)], 100.0 * 7006 / 7007),
    # the genesis save (height 0), every row new, left out
    ([save(0, 0, rows=2002, rows_kept=0, full_sets_written=1),
      save(10, 1, rows=3003, rows_kept=3003)], 100.0),
    # nothing kept
    ([save(0, 3, rows=10, rows_kept=0)], 0.0),
    # a span with no `rows` (a program that does not count them): nothing to read in it
    ([save(0, 5, full_sets_written=0)], None),
    ([save(0, 5, full_sets_written=0), save(10, 6, rows=100, rows_kept=99)], 99.0),
    # no save of a block in the window
    ([save(0, 0, rows=2002, rows_kept=0)], None),
    ([], None),
], ids=["a_key_brought", "genesis_left_out", "none_kept", "no_rows", "no_rows_and_counting",
        "genesis_only", "empty_window"])
def test_the_share_of_rows_kept(spans, share):
    got = valset_rows_kept_share.read(ctx_of(spans))
    assert got == (None if share is None else pytest.approx(share))


def test_a_window_of_spans_without_the_clock_reads_nothing(monkeypatch):
    """`window_spans.window` drops every span where none carries
    `cpu_us`, as on a program whose spans have no second clock."""
    from tendermint_tpu import trace

    events = [{"ph": "X", "name": "state.save", "ts": 0, "dur": 4000, "tid": 1,
               "args": {"height": 3, "rows": 10, "rows_kept": 9}}]
    monkeypatch.setattr(trace, "export", lambda: {"traceEvents": events})
    assert valset_rows_kept_share.read({}) is None
    events[0]["args"]["cpu_us"] = 1000.0
    assert valset_rows_kept_share.read({}) == pytest.approx(90.0)


def test_a_traced_rehearsal_of_the_churn_cell_reads_one_row_in_a_block_written_anew(churn_root, capsys):
    """16 validators: 17 rows a set, four sets a block (the next set
    whole into the index too), of them one joiner not seen before."""
    code, result = run_cell(churn_root, CELL, seed=11, seconds=3.0, trace=1, capsys=capsys)
    assert code == 0 and result["correct"] is True
    share = result["metrics"]["valset_rows_kept_share.sync"]["value"]
    assert 100.0 * 66 / 68 <= share < 100.0
