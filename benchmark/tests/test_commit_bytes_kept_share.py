"""`commit_bytes_kept_share.sync` on hand-made windows, the arithmetic,
and in a traced rehearsal of `blocksync-1k`. The window's
`blocksync.parts` and `blocksync.save_block` spans as
`window_spans.window` hands them over; a span without `kept` is a
program that does not count its commit reads."""

import pytest
from conftest import run_cell

from benchmark.metrics import commit_bytes_kept_share


def span(name, t0, cpu=True, **args):
    if cpu:
        args["cpu_us"] = 1000.0
    return {"name": name, "cat": "blocksync", "tid": 1, "t0": t0 * 1e6, "t1": (t0 + 4) * 1e6,
            "args": args}


def parts(t0, **args):
    return span("blocksync.parts", t0, **args)


def save(t0, **args):
    return span("blocksync.save_block", t0, **args)


def ctx_of(spans):
    return {"window_spans": {"spans": spans, "tnames": {1: "reactor"}}}


@pytest.mark.parametrize("spans,share", [
    # a block's steady state: its commit encoded in its parts, then its
    # size, its last commit and the seen commit read kept
    ([parts(0, height=7, encoded=1, kept=0), save(10, height=6, encoded=0, kept=3)], 75.0),
    # two blocks, one parts span whose block found its commit encoded
    ([parts(0, height=7, encoded=1, kept=0), save(10, height=6, encoded=0, kept=3),
      parts(20, height=8, encoded=0, kept=1), save(30, height=7, encoded=0, kept=3)], 87.5),
    # a program that encodes every read
    ([parts(0, height=7, encoded=1, kept=0), save(10, height=6, encoded=3, kept=0)], 0.0),
    # another span with `kept` is not read
    ([span("state.save", 0, kept=9), save(10, height=6, encoded=1, kept=1)], 50.0),
    # spans with no `kept` (a program that does not count them): nothing to read
    ([parts(0, height=7), save(10, height=6)], None),
    ([parts(0, height=7, encoded=0, kept=0)], None),
    ([], None),
], ids=["steady_block", "two_blocks", "none_kept", "other_span", "no_kept", "no_reads",
        "empty_window"])
def test_the_share_of_commit_reads_kept(spans, share):
    got = commit_bytes_kept_share.read(ctx_of(spans))
    assert got == (None if share is None else pytest.approx(share))


def test_a_window_of_spans_without_the_clock_reads_nothing(monkeypatch):
    """`window_spans.window` drops every span where none carries
    `cpu_us`, as on a program whose spans have no second clock."""
    from tendermint_tpu import trace

    events = [{"ph": "X", "name": "blocksync.save_block", "ts": 0, "dur": 4000, "tid": 1,
               "args": {"height": 3, "encoded": 1, "kept": 3}}]
    monkeypatch.setattr(trace, "export", lambda: {"traceEvents": events})
    assert commit_bytes_kept_share.read({}) is None
    events[0]["args"]["cpu_us"] = 1000.0
    assert commit_bytes_kept_share.read({}) == pytest.approx(75.0)


def test_a_traced_rehearsal_of_the_blocksync_cell_reads_most_commit_reads_kept(tiny_root, capsys):
    """One joiner from one peer: a block's commit is encoded once, in the
    verify-ahead's parts, and read kept three times in the save; a pass's
    first block encodes one more, in the parts its own iteration makes.
    A few blocks in the rehearsal's window, so the share lies under the
    three in four of the steady state."""
    code, result = run_cell(tiny_root, "blocksync-1k", seed=2147483677, seconds=3.0, trace=1,
                            capsys=capsys)
    assert code == 0 and result["correct"] is True
    share = result["metrics"]["commit_bytes_kept_share.sync"]["value"]
    assert 50.0 <= share < 75.0
