"""The readers of a span's second clock (PR 35: `cpu_us`, `offcpu_us`,
`runq_us`) on hand-written span lists, and one traced rehearsal of a
light and of a sync cell: control flow and arithmetic only."""

import importlib
import json
import re

import pytest

from benchmark import window_spans

LIGHT = ("dispatch_offcpu_ms_per_launch.light", "caller_offcpu_share.light",
         "slowest_op_ms.light", "slowest_op_offcpu_share.light")
SYNC = tuple(name.replace(".light", ".sync") for name in LIGHT)
ALL = ("dispatch_offcpu_ms_per_launch", "caller_offcpu_share", "slowest_op_ms",
       "slowest_op_offcpu_share", "refusal_gap_ms", "refusal_gap_starved_share")


def read(metric: str, ctx: dict):
    return importlib.import_module("benchmark.metrics." + metric).read(ctx)


IDS = iter(range(1, 10**6))


def span(name, t0_ms, t1_ms, cpu_ms=None, tid=1, runq_ms=None, **args):
    """A span of the program as `window_spans.window` hands it over;
    `cpu_ms` None is a span without the second clock."""
    args.setdefault("span", next(IDS))
    args.setdefault("parent", 0)
    args.setdefault("req", args["span"])
    if cpu_ms is not None:
        args["cpu_us"] = cpu_ms * 1e3
        args["offcpu_us"] = max(0.0, (t1_ms - t0_ms - cpu_ms) * 1e3)
    if runq_ms is not None:
        args["runq_us"] = runq_ms * 1e3
    return {"name": name, "cat": "x", "t0": t0_ms * 1e6, "t1": t1_ms * 1e6, "tid": tid,
            "args": args}


def below(parent, name, t0_ms, t1_ms, cpu_ms=None, **args):
    return span(name, t0_ms, t1_ms, cpu_ms, tid=args.pop("tid", parent["tid"]),
                parent=parent["args"]["span"], req=parent["args"]["req"], **args)


def ctx_of(window):
    return {"window_spans": {"spans": window, "tnames": {1: "reactor", 2: "engine-dispatch"}}}


def an_update(t0, cpu_ms, collect_ms, runq_ms=None, with_clock=True):
    """A light update of 40 ms under the harness's span: `cpu_ms` on the
    CPU, `collect_ms` asleep in `verify.commit_collect`, the rest off
    the CPU without meaning to be."""
    clock = (lambda ms: ms) if with_clock else (lambda ms: None)
    outer = span("bench.update", t0, t0 + 41, clock(cpu_ms + 0.5), runq_ms=runq_ms)
    outer["cat"] = "bench"
    root = below(outer, "light.update", t0 + 0.5, t0 + 40.5, clock(cpu_ms), height=7)
    step = below(root, "light.verify_step", t0 + 10, t0 + 30)  # not clocked where the clock is dear
    collect = below(step, "verify.commit_collect", t0 + 12, t0 + 12 + collect_ms, clock(0.0))
    # another request's collect, and this request's on the engine's thread: not the root's waits
    other = span("verify.commit_collect", t0 + 1, t0 + 9, clock(0.0))
    elsewhere = below(root, "verify.commit_collect", t0 + 1, t0 + 9, clock(0.0), tid=2)
    return [outer, root, step, collect, other, elsewhere]


def test_the_callers_share_takes_the_wait_for_verdicts_out():
    # (40 - 20 - 12) + (40 - 28 - 12) of 80 ms
    ctx = ctx_of(an_update(0, cpu_ms=20, collect_ms=12) + an_update(50, cpu_ms=28, collect_ms=12))
    assert read("caller_offcpu_share", ctx) == pytest.approx(100.0 * 8 / 80)


def test_a_clock_that_counts_in_ticks_is_summed_before_it_is_floored():
    """Ten updates of 4 ms that ran throughout, on a clock of 10 ms
    ticks: four read a tick each, six none. Span by span the six would
    read as 24 ms off the CPU; summed, 40 ms of CPU against 40 of wall."""
    window = [span("light.update", 10 * i, 10 * i + 4, 10.0 if i < 4 else 0.0) for i in range(10)]
    window += [span("ops.verify_dispatch", 10 * i + 5, 10 * i + 7, 10.0 if i < 2 else 0.0, tid=2)
               for i in range(10)]
    assert read("caller_offcpu_share", ctx_of(window)) == pytest.approx(0.0)
    assert read("dispatch_offcpu_ms_per_launch", ctx_of(window)) == pytest.approx(0.0)


def test_a_joiners_root_is_a_try_sync_that_applied():
    applied = span("blocksync.try_sync", 0, 60, 45, applied=True)
    collect = below(applied, "verify.commit_collect", 5, 10, 0.0)
    poll = span("blocksync.try_sync", 60, 80, 0.01, applied=False)
    ctx = ctx_of([applied, collect, poll])
    assert read("caller_offcpu_share", ctx) == pytest.approx(100.0 * (15 - 5) / 60)


def test_dispatch_offcpu_is_per_dispatch_span_of_the_whole_window():
    window = [span("ops.verify_dispatch", 0, 14, 4, tid=2),
              span("ops.msm_dispatch", 20, 34, 13, tid=2),
              span("ops.verify_dispatch", 40, 54, tid=2),  # unclocked: not read
              span("ops.prep", 1, 5, 1, tid=2)]
    assert read("dispatch_offcpu_ms_per_launch", ctx_of(window)) == pytest.approx(5.5)
    assert read("dispatch_offcpu_ms_per_launch", ctx_of([span("ops.prep", 1, 5, 1)])) is None


def a_refusal(t0, with_clock=True):
    """A refusing iteration of 20 ms, two starved stretches, the first
    block applied again 800 ms after the refusal began."""
    clock = (lambda ms: ms) if with_clock else (lambda ms: None)
    refusing = span("blocksync.try_sync", t0 - 15, t0 + 5, clock(18), runq_ms=clock(0.1),
                    applied=False, refused=True)
    refuse = below(refusing, "blocksync.refuse", t0, t0 + 4, clock(3.5), height=9, stage="commit",
                   dropped=70)
    polls = [span("blocksync.try_sync", t0 + 100 * i, t0 + 100 * i + 0.02, clock(0.02),
                  runq_ms=clock(0.0), applied=False, refused=False) for i in range(1, 7)]
    back, later = (span("blocksync.try_sync", t0 + at, t0 + at + 60, clock(50), runq_ms=clock(1.0),
                        applied=True) for at in (740, 800))
    starved = [span("blocksync.starved", t0 + 5, t0 + 400, polls=3),
               span("blocksync.starved", t0 + 400, t0 + 800, polls=3),
               span("blocksync.starved", t0 + 100, t0 + 300, tid=3)]  # another joiner's
    decode = span("p2p.recv", t0 + 50, t0 + 700, tid=2)
    return [refusing, refuse, *polls, back, later, *starved, decode]


def test_a_refusal_gap_runs_to_the_first_block_applied_again(capsys):
    window = a_refusal(1000) + a_refusal(5000)
    window.append(span("blocksync.refuse", 9000, 9004, 3.5))  # the window closed before a block
    ctx = ctx_of(window)
    assert read("refusal_gap_ms", ctx) == pytest.approx(800.0)
    assert read("refusal_gap_starved_share", ctx) == pytest.approx(100.0 * 795 / 800)
    out = capsys.readouterr().out
    assert out.count("refusal gap: height=9 stage=commit dropped=70 gap=800.0ms") == 2
    # the refusing iteration's 5 ms inside the gap, six polls, the block applied again
    assert "reactor*/blocksync.try_sync: 8, 65.1, 68.1" in out
    assert "engine-dispatch/p2p.recv: 1, 650.0, -" in out and "blocksync.refuse:" not in out
    assert read("refusal_gap_ms", ctx_of([])) is None
    assert read("refusal_gap_starved_share", ctx_of([])) is None


def test_the_slowest_op_is_the_windows_longest_root_and_the_log_names_what_held_it(capsys):
    window = an_update(0, cpu_ms=20, collect_ms=12, runq_ms=0.1)
    stalled = span("bench.update", 100, 2101, 31, runq_ms=1900.0)
    root = below(stalled, "light.update", 100.5, 2100.5, 30, height=11)
    fetch = below(root, "light.fetch", 101, 2050, 12)
    decode = below(fetch, "light.decode_part", 102, 2040)
    collect = below(root, "verify.commit_collect", 2060, 2070, 0.0)
    window += [stalled, root, fetch, decode, collect]
    ctx = ctx_of(window)
    assert read("slowest_op_ms", ctx) == pytest.approx(2000.0)
    assert read("slowest_op_offcpu_share", ctx) == pytest.approx(100.0 * (1970 - 10) / 2000)
    out = capsys.readouterr().out
    assert out.count("slowest op:") == 1  # found once a run, whichever reader asks first
    assert f"req={stalled['args']['span']} height=11 began=0.100s after the first " in out
    assert "dur=2000.000ms cpu_us=30000 " in out and "runq_us=1900000.0 (of bench.update)" in out
    assert ("light.fetch dur=1949000us cpu_us=12000 > light.decode_part dur=1938000us cpu_us=-"
            in out)


def as_events(spans):
    """The spans as `trace.export()` holds them."""
    return [{"name": sp["name"], "cat": "x", "ph": "X", "ts": sp["t0"] / 1e3,
             "dur": (sp["t1"] - sp["t0"]) / 1e3, "tid": sp["tid"], "args": sp["args"]}
            for sp in spans]


def test_a_program_without_the_second_clock_gives_every_reader_nothing(monkeypatch):
    spans = (an_update(0, 20, 12, with_clock=False) + a_refusal(1000, with_clock=False)
             + [span("ops.verify_dispatch", 0, 14, tid=2)])
    for sp in spans:  # as the harness hands the slice's spans over
        sp["ends_in_slice"] = True
    # ... and the whole window's helper, asked of such a program's ring, keeps no span
    from tendermint_tpu import trace

    events = as_events(spans)
    monkeypatch.setattr(trace, "export", lambda: {"traceEvents": events})
    ctx = {"spans": spans}
    assert [read(metric, ctx) for metric in ALL] == [None] * len(ALL)
    assert ctx["window_spans"]["spans"] == []
    # with it, the same ring is read: exported once, kept for the other readers
    events[:] = as_events(an_update(0, 20, 12, runq_ms=1.0))
    ctx = {"spans": []}
    assert read("slowest_op_ms", ctx) == pytest.approx(40.0)
    events.clear()
    assert read("slowest_op_offcpu_share", ctx) == pytest.approx(100.0 * 8 / 40)
    assert window_spans.window(ctx)["spans"][1]["t0"] == pytest.approx(0.5e6)


@pytest.mark.parametrize("workload,names", [("light-1k-skip", LIGHT), ("blocksync-1k", SYNC)])
def test_a_traced_run_reports_every_new_name(tiny_root, capsys, workload, names):
    from benchmark import run

    code = run.main(["--workload", workload, "--seed", "5", "--seconds", "3.0", "--trace", "1"],
                    require_tpu=False, root=tiny_root)
    out = capsys.readouterr().out.strip().splitlines()
    assert code == 0
    result = json.loads(out[-1])
    assert result["correct"] is True
    values = {name: m["value"] for name, m in result["metrics"].items()}
    with open(tiny_root + "/BENCHMARK.json") as f:
        listed = {m["name"] for m in json.load(f)["per_layer"]
                  if workload in m.get("workloads", [workload])}
    assert set(names) <= listed and set(names) <= set(values), sorted(set(names) - set(values))
    kind = names[0].rsplit(".", 1)[1]
    assert 0.0 <= values[f"caller_offcpu_share.{kind}"] <= 100.0
    assert 0.0 <= values[f"slowest_op_offcpu_share.{kind}"] <= 100.0
    assert sum(line.startswith("slowest op: ") for line in out) == 1
    if kind == "light":
        # the driver's clock around the client's call and the program's span agree
        latest = next(line for line in out if line.startswith("latencies: "))
        assert values["slowest_op_ms.light"] == pytest.approx(
            float(re.search(r"max=([0-9.]+)ms", latest).group(1)), abs=1.0)


def test_several_seeds_in_one_process_each_traced_or_not(tiny_root, capsys):
    from benchmark.tools import many_traced

    code = many_traced.main(["--workload", "light-150-skip", "--seeds", "5,6:off", "--seconds", "2"],
                            require_tpu=False, root=tiny_root)
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines() if ln.startswith("{")]
    assert code == 0 and [(ln["seed"], ln["traced"], ln["correct"]) for ln in lines] == [
        (5, True, True), (6, False, True)]
    assert "slowest_op_ms.light" in lines[0]["metrics"] and "light_rate" not in lines[0]["metrics"]
    assert set(lines[1]["metrics"]) == {"light_rate", "light_update_p95", "setup_s"}
