"""`launches_per_update.light` on hand-made slices: the arithmetic only."""

import pytest

from benchmark.metrics.launches_per_update import read


def span(name, t0, t1, ends=True, **args):
    return {"name": name, "cat": "x", "t0": t0 * 1e6, "t1": t1 * 1e6, "tid": 1,
            "ends_in_slice": ends, "args": args}


def update(t0, launches, ends=True):
    """One update of 10 ms and the groups the engine dispatched inside it."""
    return [span("light.update", t0, t0 + 10, ends)] + [
        span("engine.dispatch", t0 + 1 + 4 * i, t0 + 2 + 4 * i, jobs=2 // launches)
        for i in range(launches)]


@pytest.mark.parametrize("spans,want", [
    (update(0, 2) + update(10, 2) + update(20, 2), 2.0),  # each check submitted alone
    (update(0, 1) + update(10, 1) + update(20, 1), 1.0),  # both in one group
    (update(0, 1) + update(10, 2) + [span("light.update", 20, 30, mode="root"),
                                     span("engine.dispatch", 21, 22, jobs=1)], 4 / 3),
    # the update still open when the slice ends is not counted, nor its launch that is
    (update(0, 1) + [span("light.update", 10, 20, ends=False),
                     span("engine.dispatch", 11, 12, ends=False)], 1.0),
    (update(0, 1, ends=False), None),  # no update ended
    ([span("light.update", 0, 10)], None),  # a trace without the engine's spans
    ([], None),
])
def test_launches_per_update_counts_dispatches_over_updates_that_ended(spans, want):
    got = read({"spans": spans})
    assert got is None if want is None else got == pytest.approx(want)
