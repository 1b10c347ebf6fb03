"""Running or waiting: the whole window's spans, and what the readers of
a span's second clock share.

A program span of an enabled run carries `cpu_us` (its thread's CPU
time inside it) and `offcpu_us` (its duration less that: blocked, or
runnable with no core); a thread's outermost span also `runq_us`, the
run-queue part of it, where the kernel keeps a schedstat
(docs/observability.md). A program without them, as every commit before
PR 35, gives every reader here nothing to read.

Every reader here goes through `window(ctx)`: the traced run's ring is
cleared before the window and switched off when it closes, so at reader
time `trace.export()` holds the window and nothing else. It is exported
once a run and kept in `ctx`, in the shape of `ctx["spans"]`
(readers.py) with `t0` and `t1` as they were, not clipped to any slice.
The whole window and not the slice, because the chip's machine counts a
thread's CPU time in ticks of 10 ms (my chip runs, PR 35): one span's
`cpu_us` is a sample there, 0 or 10000 for a span of 2 ms, and only a
sum over seconds of spans reads true. For the same reason time off the
CPU is summed here as durations less CPU times and floored once, not as
the spans' own `offcpu_us`, each floored at 0, which a coarse clock
biases upwards.

The request's root is `light.update` for a light client and a
`blocksync.try_sync` that applied a block for a joiner; a cell has one
kind. A root waits by design in one place, `verify.commit_collect` (the
verdicts of a launch): what is left of its time off the CPU once the
collects of its `req` on its thread are taken out is time it did not
mean to wait.
"""

from __future__ import annotations

DISPATCHERS = ("ops.verify_dispatch", "ops.msm_dispatch")
COLLECT = "verify.commit_collect"


def window(ctx: dict) -> dict:
    """{"spans": [...], "tnames": {tid: thread name}} of the whole
    window; no span where the program's carry no `cpu_us`."""
    if "window_spans" not in ctx:
        from tendermint_tpu import trace

        spans, tnames = [], {}
        for ev in trace.export()["traceEvents"]:
            if ev.get("ph") == "X":
                spans.append({"name": ev["name"], "cat": ev.get("cat", ""), "tid": ev["tid"],
                              "t0": ev["ts"] * 1e3, "t1": (ev["ts"] + ev["dur"]) * 1e3,
                              "args": ev.get("args", {})})
            elif ev.get("ph") == "M" and ev.get("name") == "thread_name":
                tnames[ev["tid"]] = ev["args"]["name"]
        if not any("cpu_us" in sp["args"] for sp in spans):
            spans = []
        ctx["window_spans"] = {"spans": spans, "tnames": tnames}
    return ctx["window_spans"]


def clocked(spans: list[dict], *names: str) -> list[dict]:
    return [sp for sp in spans if sp["name"] in names and "cpu_us" in sp["args"]]


def offcpu_ns(spans: list[dict]) -> float:
    """Nanoseconds these spans' threads were inside them and not on a
    core: durations less CPU times, floored once."""
    return max(0.0, sum(sp["t1"] - sp["t0"] - sp["args"]["cpu_us"] * 1e3 for sp in spans))


def roots(spans: list[dict]) -> list[dict]:
    return [sp for sp in clocked(spans, "light.update", "blocksync.try_sync")
            if sp["name"] == "light.update" or sp["args"].get("applied")]


def unmeant_offcpu_share(some_roots: list[dict], spans: list[dict]) -> float | None:
    """100 x the roots' time off the CPU, less that of the
    `verify.commit_collect` spans of their `req` on their thread, over
    the roots' duration."""
    if not some_roots:
        return None
    mine = {(r["args"].get("req"), r["tid"]) for r in some_roots}
    collects = [sp for sp in clocked(spans, COLLECT) if (sp["args"].get("req"), sp["tid"]) in mine]
    return (100.0 * max(0.0, offcpu_ns(some_roots) - offcpu_ns(collects))
            / sum(r["t1"] - r["t0"] for r in some_roots))


def slowest(ctx: dict) -> dict | None:
    """The window's longest root: {"ms", "offcpu_share"}. When it began,
    its `req`, the `runq_us` of the thread's outermost span around it
    and the longest child at each level below it go to the run's log,
    since a number in a result cannot name a span. None where no root."""
    if "slowest_op" not in ctx:
        spans = window(ctx)["spans"]
        found = roots(spans)
        ctx["slowest_op"] = None
        if found:
            root = max(found, key=lambda sp: sp["t1"] - sp["t0"])
            share = unmeant_offcpu_share([root], spans)
            by_id, children = {}, {}
            for sp in spans:
                by_id[sp["args"].get("span")] = sp
                children.setdefault(sp["args"].get("parent"), []).append(sp)
            carrier = root  # of runq_us: the root, or its nearest ancestor on the thread with one
            while carrier is not None and "runq_us" not in carrier["args"]:
                carrier = by_id.get(carrier["args"].get("parent"))
                if carrier is not None and carrier["tid"] != root["tid"]:
                    carrier = None
            path, at = [], root
            while children.get(at["args"].get("span")):
                at = max(children[at["args"]["span"]], key=lambda sp: sp["t1"] - sp["t0"])
                path.append(at)
            def cpu(sp):
                return format(sp["args"]["cpu_us"], ".0f") if "cpu_us" in sp["args"] else "-"

            print(f"slowest op: {root['name']} req={root['args'].get('req')} "
                  f"height={root['args'].get('height')} "
                  f"began={(root['t0'] - min(r['t0'] for r in found)) / 1e9:.3f}s after the first "
                  f"dur={(root['t1'] - root['t0']) / 1e6:.3f}ms "
                  f"cpu_us={cpu(root)} unmeant_offcpu_share={share:.2f}% "
                  f"runq_us={carrier['args']['runq_us'] if carrier else None}"
                  f"{' (of ' + carrier['name'] + ')' if carrier and carrier is not root else ''}; "
                  "longest child at each level: " + " > ".join(
                      f"{sp['name']} dur={(sp['t1'] - sp['t0']) / 1e3:.0f}us "
                      f"cpu_us={cpu(sp)}" for sp in path),
                  flush=True)
            ctx["slowest_op"] = {"ms": (root["t1"] - root["t0"]) / 1e6, "offcpu_share": share}
    return ctx["slowest_op"]


def refusal_gaps(ctx: dict) -> list[dict]:
    """One {"gap_ns", "starved_ns"} a `blocksync.refuse` of the window:
    from its start to the end of the first `blocksync.try_sync` with
    `applied` that starts after it on its thread, and the part of that
    inside `blocksync.starved`. A refusal no applied block follows (the
    window closed first) gives none. What each thread's outermost spans
    did inside each gap goes to the run's log."""
    if "refusal_gaps" not in ctx:
        win = window(ctx)
        spans, gaps = win["spans"], []
        applied = roots(spans)
        tid_of = {sp["args"].get("span"): sp["tid"] for sp in spans}
        for refuse in (sp for sp in spans if sp["name"] == "blocksync.refuse"):
            tid, start = refuse["tid"], refuse["t0"]
            ends = [sp["t1"] for sp in applied if sp["tid"] == tid and sp["t0"] >= start]
            if not ends:
                continue
            end = min(ends)
            starved = sum(max(0.0, min(sp["t1"], end) - max(sp["t0"], start)) for sp in spans
                          if sp["name"] == "blocksync.starved" and sp["tid"] == tid)
            gaps.append({"gap_ns": end - start, "starved_ns": starved})
            inside: dict = {}  # (thread, span name) -> [n, ms inside the gap, cpu ms or None]
            for sp in spans:
                if (sp["t1"] > start and sp["t0"] < end and sp["name"] != "blocksync.starved"
                        and tid_of.get(sp["args"].get("parent")) != sp["tid"]):
                    who = (win["tnames"].get(sp["tid"], str(sp["tid"]))
                           + ("*" if sp["tid"] == tid else ""), sp["name"])
                    row = inside.setdefault(who, [0, 0.0, None])
                    row[0] += 1
                    row[1] += (min(sp["t1"], end) - max(sp["t0"], start)) / 1e6
                    if "cpu_us" in sp["args"]:
                        row[2] = (row[2] or 0.0) + sp["args"]["cpu_us"] / 1e3
            print(f"refusal gap: height={refuse['args'].get('height')} "
                  f"stage={refuse['args'].get('stage')} dropped={refuse['args'].get('dropped')} "
                  f"gap={(end - start) / 1e6:.1f}ms starved={starved / 1e6:.1f}ms "
                  f"refuse_span={(refuse['t1'] - refuse['t0']) / 1e6:.2f}ms; each thread's "
                  "outermost spans overlapping it (thread/name: n, ms inside, their cpu ms; "
                  "* the reactor's): " + "; ".join(
                      f"{who[0]}/{who[1]}: {row[0]}, {row[1]:.1f}, "
                      f"{'-' if row[2] is None else format(row[2], '.1f')}" for who, row in
                      sorted(inside.items(), key=lambda kv: -kv[1][1])[:8]), flush=True)
        ctx["refusal_gaps"] = gaps
    return ctx["refusal_gaps"]
