"""tmpath — block-journey tracing + per-height critical-path
attribution (lens/journey.py, docs/observability.md#tmpath).

Deterministic journey fixtures: two synthetic nodes with a known stamp
sequence (the exact event shapes the consensus plane emits, pinned
against a LIVE single-validator run below) exercise flow-id stability,
unstamped-frame byte-identity, decomposition tiling, cross-node arrow
synthesis, the journey_stall gate, and the critical-path CLI rc paths.
The committed fixture run-dir (tests/testdata/journey_run) smoke-tests
the offline CLI against bytes that cannot drift with the builders.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(__file__))

from tendermint_tpu import trace as T
from tendermint_tpu.lens.gates import DEFAULT_GATES
from tendermint_tpu.lens.journey import (
    STAGES,
    critical_path,
    fleet_critical_path,
    journey_height,
)
from tendermint_tpu.lens.traces import journey_flow_events, merge_traces

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURE_RUN = os.path.join(os.path.dirname(__file__), "testdata", "journey_run")

US = 1e6


# ------------------------------------------------- deterministic fixtures


def synth_node_events(
    name: str,
    proposer: bool,
    base_us: float = 0.0,
    heights=(1, 2, 3),
    block_us: float = 1_000_000.0,
    quorum_dur_us: float = 500_000.0,
) -> list[dict]:
    """One synthetic node's journey events with a KNOWN stamp sequence —
    the same names/args/phases the consensus plane emits live."""
    jk = T.journey_key
    evs: list[dict] = []
    t = base_us
    for h in heights:
        t0 = t
        if proposer:
            evs.append({"name": "journey.proposal_build", "ph": "X",
                        "ts": t0 + 0.01 * US, "dur": 0.20 * US, "tid": 1,
                        "args": {"height": h, "round": 0, "parts": 2,
                                 "journey": jk(h, 0, "block", name)}})
            evs.append({"name": "journey.send", "ph": "i", "ts": t0 + 0.22 * US,
                        "tid": 1, "args": {"height": h, "type": "proposal",
                                           "journey": jk(h, 0, "proposal", "nodeA")}})
        else:
            evs.append({"name": "journey.recv", "ph": "i", "ts": t0 + 0.24 * US,
                        "tid": 1, "args": {"height": h, "type": "proposal",
                                           "journey": jk(h, 0, "proposal", "nodeA")}})
        # the receiver accepts the proposal a beat after the proposer
        # (propagation) — also keeps merge-tie-breaking deterministic
        evs.append({"name": "journey.proposal", "ph": "i",
                    "ts": t0 + (0.25 if proposer else 0.27) * US,
                    "tid": 1, "args": {"height": h, "round": 0,
                                       "journey": jk(h, 0, "proposal", "nodeA")}})
        evs.append({"name": "journey.block_assembled", "ph": "X",
                    "ts": t0 + 0.26 * US, "dur": 0.10 * US, "tid": 1,
                    "args": {"height": h, "round": 0, "parts": 2,
                             "journey": jk(h, 0, "block", "nodeA")}})
        evs.append({"name": "verify.commit_dispatch", "ph": "X",
                    "ts": t0 + 0.40 * US, "dur": 0.05 * US, "tid": 1,
                    "args": {"height": h - 1, "nsigs": 4}})
        evs.append({"name": "verify.commit_collect", "ph": "X",
                    "ts": t0 + 0.45 * US, "dur": 0.15 * US, "tid": 1,
                    "args": {"height": h - 1, "nsigs": 4}})
        evs.append({"name": "journey.quorum", "ph": "X", "ts": t0 + 0.30 * US,
                    "dur": quorum_dur_us, "tid": 1,
                    "args": {"height": h, "round": 0, "type": "precommit",
                             "journey": jk(h, 0, "precommit", "")}})
        evs.append({"name": "consensus.finalize_commit", "ph": "X",
                    "ts": t0 + 0.85 * US, "dur": 0.15 * US, "tid": 1,
                    "args": {"height": h, "round": 0,
                             "journey": jk(h, 0, "commit", "")}})
        t += block_us
    return evs


def write_run_dir(path, nodes: dict[str, list[dict]]) -> str:
    for name, events in nodes.items():
        d = os.path.join(str(path), name)
        os.makedirs(d, exist_ok=True)
        with open(os.path.join(d, "trace.json"), "w") as f:
            json.dump({"traceEvents": events, "displayTimeUnit": "ms"}, f)
    return str(path)


def _tmlens_main():
    spec = importlib.util.spec_from_file_location(
        "tmlens_cli_journey", os.path.join(_ROOT, "scripts", "tmlens.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.main


# ------------------------------------------------------ flow-id stability


def test_journey_key_deterministic_and_parseable():
    a = T.journey_key(7, 2, "vote", "aabbccddeeff00112233")
    b = T.journey_key(7, 2, "vote", "aabbccddeeff00112233")
    assert a == b == "7/2/vote@aabbccddeeff0011"  # origin truncated at 16
    assert T.journey_key(7, 2, "vote", "") == "7/2/vote@-"
    assert a != T.journey_key(7, 3, "vote", "aabbccddeeff00112233")
    assert journey_height(a) == 7
    assert journey_height("garbage") is None


def test_sender_and_receiver_derive_identical_keys():
    """The frame's origin_node stamp is all the receiver needs: after a
    codec round trip, both ends compute the same journey key."""
    from tendermint_tpu.consensus.messages import VoteMessage
    from tendermint_tpu.consensus.reactor import (
        decode_consensus_msg,
        encode_consensus_msg,
    )
    from tendermint_tpu.types.vote import PREVOTE, Vote

    vote = Vote(type=PREVOTE, height=9, round=1, validator_address=b"\x01" * 20,
                validator_index=1, signature=b"\x02" * 64)
    sender_key = T.journey_key(9, 1, "vote", "deadbeef00112233")
    rt = decode_consensus_msg(
        encode_consensus_msg(VoteMessage(vote), "deadbeef00112233")
    )
    assert rt.origin_node == "deadbeef00112233"
    assert T.journey_key(rt.vote.height, rt.vote.round, "vote", rt.origin_node) == sender_key


def test_unstamped_frames_stay_byte_identical():
    """origin_node ("" omitted, field 1001) follows the origin_ns
    precedent: unstamped frames encode byte-identically to the
    reference schema, and a decoder that knows neither field skips
    both."""
    from tendermint_tpu.proto import messages as pb
    from tendermint_tpu.proto.message import Message
    from tendermint_tpu.types.vote import PREVOTE, Vote

    vote = Vote(type=PREVOTE, height=3, round=0, validator_address=b"\x01" * 20,
                validator_index=1, signature=b"\x02" * 64).to_proto()
    bare = pb.ConsensusMessage(vote=pb.CsVote(vote=vote)).encode()
    explicit = pb.ConsensusMessage(
        vote=pb.CsVote(vote=vote), origin_ns=0, origin_node=""
    ).encode()
    assert bare == explicit

    # a reference-schema decoder (fields 1-9 only) skips the stamps
    class RefConsensusMessage(Message):
        fields = [f for f in pb.ConsensusMessage.fields if f.number < 1000]

    stamped = pb.ConsensusMessage(
        vote=pb.CsVote(vote=vote), origin_ns=123456789, origin_node="aa" * 8
    ).encode()
    assert stamped != bare
    decoded = RefConsensusMessage.decode(stamped)
    assert decoded.vote is not None
    assert decoded.vote.vote.encode() == vote.encode()


# -------------------------------------------------- decomposition tiling


def test_decomposition_tiles_block_interval_exactly():
    events = synth_node_events("nodeA", proposer=True)
    cp = critical_path(events)
    assert sorted(cp["heights"]) == [1, 2, 3]
    for h, e in cp["heights"].items():
        total = sum(e["stages"][s] for s in STAGES)
        assert total == pytest.approx(e["interval_s"], rel=1e-6), (h, e)
    # heights 2,3 have the previous commit anchor: exactly 1.0s windows
    e2 = cp["heights"][2]
    assert "missing" not in e2
    assert e2["interval_s"] == pytest.approx(1.0)
    assert e2["stages"]["proposer"] == pytest.approx(0.25)   # commit end -> proposal
    assert e2["stages"]["gossip"] == pytest.approx(0.11)     # proposal -> assembled end
    assert e2["stages"]["verify"] == pytest.approx(0.20)     # the two verify spans
    assert e2["stages"]["quorum"] == pytest.approx(0.24)     # (0.8-0.36) - 0.2
    assert e2["stages"]["apply"] == pytest.approx(0.20)      # quorum end -> commit end
    assert e2["dominant"] == "proposer"
    assert e2["proposer_build_s"] == pytest.approx(0.20)
    # height 1 has no previous commit: judged from partial anchors
    assert "prev_commit" in cp["heights"][1].get("missing", [])
    # totals + fleet digest
    assert cp["totals"]["heights"] == 3
    assert cp["totals"]["proposed_heights"] == 3
    fleet = fleet_critical_path([
        ("nodeA", cp), ("nodeB", critical_path(synth_node_events("nodeB", False, 7 * US))),
    ])
    assert fleet["nodes"] == 2 and fleet["heights_covered"] == 3
    assert fleet["proposer_builds"] == 3
    assert fleet["worst"]["seconds"] >= fleet["stage_fractions"]["proposer"] > 0


def test_decomposition_handles_missing_anchors_and_clamps():
    # quorum + assembly absent: stage falls back to commit_start, no
    # negatives anywhere
    jk = T.journey_key
    evs = []
    for h in (1, 2):
        t0 = h * US
        evs.append({"name": "journey.proposal", "ph": "i", "ts": t0 + 0.9 * US,
                    "tid": 1, "args": {"height": h, "round": 0,
                                       "journey": jk(h, 0, "proposal", "x")}})
        evs.append({"name": "consensus.finalize_commit", "ph": "X",
                    "ts": t0 + 0.95 * US, "dur": 0.05 * US, "tid": 1,
                    "args": {"height": h, "round": 0}})
    cp = critical_path(evs)
    e = cp["heights"][2]
    assert {"assembled", "precommit_quorum"} <= set(e["missing"])
    assert all(v >= 0 for v in e["stages"].values())
    assert sum(e["stages"].values()) == pytest.approx(e["interval_s"], rel=1e-6)
    # an empty trace yields no heights (and analyze treats it as absent)
    assert critical_path([]) == {"heights": {}, "totals": {"heights": 0}}


# ------------------------------------------------- live emission pinning


def test_live_single_validator_emits_journey_spans_that_tile():
    """A REAL consensus node (in-process, kvstore) with tracing on must
    emit the journey span set this suite's synthetic fixtures assume,
    and its real critical path must tile each block interval within the
    15% acceptance tolerance."""
    from helpers import make_genesis_doc, make_keys
    from test_consensus import fast_params, make_node, wait_for_height

    keys = make_keys(1)
    gen_doc = make_genesis_doc(keys, "journey-live")
    gen_doc.consensus_params = fast_params()
    was = T.enabled()
    T.clear()
    T.set_enabled(True)
    node = make_node(keys, 0, gen_doc)
    node.node_id = "aa" * 20
    node.start()
    try:
        assert wait_for_height([node], 3, timeout=30)
    finally:
        node.stop()
        T.set_enabled(was)
    events = T.export()["traceEvents"]
    T.clear()
    names = {e["name"] for e in events}
    assert {"journey.proposal_build", "journey.proposal",
            "journey.block_assembled", "journey.quorum",
            "consensus.finalize_commit"} <= names, names
    # finalize spans carry the shared commit journey key
    fin = [e for e in events if e["name"] == "consensus.finalize_commit"
           and e.get("ph") == "X"]
    assert all((e.get("args") or {}).get("journey", "").endswith("/commit@-")
               for e in fin)
    cp = critical_path(events)
    full = {h: e for h, e in cp["heights"].items()
            if "missing" not in e and e["interval_s"] > 0}
    assert full, cp["heights"]
    for h, e in full.items():
        total = sum(e["stages"][s] for s in STAGES)
        assert total == pytest.approx(e["interval_s"], rel=0.15, abs=1e-4), (h, e)
    # the single validator proposed every height it committed
    assert cp["totals"]["proposed_heights"] >= len(full)


def test_engine_journey_passthrough():
    """A journey-tagged engine submit surfaces the tag on the coalesced
    launch's collect span (the attribution the lens verify split
    reads)."""
    from tendermint_tpu.crypto import ed25519_ref as ref
    from tendermint_tpu.ops.engine import get_engine

    sk = ref.gen_privkey(b"\x11" * 32)
    pk, msg = sk[32:], b"tmpath-journey-probe"
    sig = ref.sign(sk, msg)
    tag = T.journey_key(42, 0, "verify", "")
    was = T.enabled()
    T.set_enabled(True)
    try:
        handle = get_engine().submit("ed25519", [pk], [msg], [sig], journey=tag)
        assert handle.result(timeout=60) == [True]
    finally:
        T.set_enabled(was)
    events = T.export()["traceEvents"]
    collects = [e for e in events if e["name"] == "engine.collect"
                and tag in ((e.get("args") or {}).get("journeys") or [])]
    assert collects, "journey tag did not reach the engine collect span"
    assert journey_height(tag) == 42


def test_verify_commit_tags_the_engine_with_its_height():
    """verify_commit tags its batch verifier with the commit's journey
    key (types/validation.py), and the tag survives coalescing onto the
    engine's collect span — the exact chain lens/journey.py's
    host-vs-engine verify split reads."""
    from helpers import make_block_id, make_keys, make_validator_set, sign_commit
    from tendermint_tpu.crypto import BatchVerifier
    from tendermint_tpu.types.validation import verify_commit

    assert BatchVerifier.journey is None  # default: untagged
    keys = make_keys(4)
    vals = make_validator_set(keys)
    block_id = make_block_id()
    commit = sign_commit("journey-bv", vals, keys, height=5, round_=0,
                         block_id=block_id)
    was = T.enabled()
    T.set_enabled(True)
    T.clear()
    try:
        verify_commit("journey-bv", vals, block_id, 5, commit)
    finally:
        T.set_enabled(was)
    events = T.export()["traceEvents"]
    T.clear()
    tag = T.journey_key(5, 0, "verify", "")
    dispatch = [e for e in events if e["name"] == "verify.commit_dispatch"]
    assert dispatch and dispatch[0]["args"]["height"] == 5
    tagged = [e for e in events if e["name"] in ("engine.dispatch", "engine.collect")
              and tag in ((e.get("args") or {}).get("journeys") or [])]
    assert tagged, "commit journey tag never reached an engine span"


# ------------------------------------------------------ cross-node flows


def test_merged_trace_draws_cross_node_journey_arrows():
    a = synth_node_events("nodeA", proposer=True)
    b = synth_node_events("nodeB", proposer=False, base_us=7 * US)
    doc, offsets = merge_traces([("nodeA", a), ("nodeB", b)])
    assert offsets[1] is not None
    flows = [e for e in doc["traceEvents"] if e.get("cat") == "tm.journey"]
    assert flows, "no journey arrows in merged trace"
    # every committed height contributes at least one cross-node flow
    flow_heights = {journey_height(e["id"]) for e in flows}
    assert {1, 2, 3} <= flow_heights
    # arrow ids are the deterministic journey keys — NOT pid-namespaced
    # (cross-node binding is the point), while counter ids still are
    assert all(":" not in str(e["id"]) for e in flows)
    for e in flows:
        assert e["ph"] in ("s", "f") and e["pid"] in (1, 2)
    # start on the earliest event's pid, finish on the latest's
    prop1 = [e for e in flows if e["id"] == T.journey_key(1, 0, "proposal", "nodeA")]
    assert {e["ph"] for e in prop1} == {"s", "f"}
    s = next(e for e in prop1 if e["ph"] == "s")
    f = next(e for e in prop1 if e["ph"] == "f")
    assert s["pid"] == 1 and f["pid"] == 2  # sender's instant precedes receiver's


def test_single_node_journeys_draw_no_arrows():
    a = synth_node_events("nodeA", proposer=True)
    assert journey_flow_events([dict(e, pid=1) for e in a]) == []


# ------------------------------------------------------------------ gates


def test_journey_stall_gate_names_node_height_and_stage(tmp_path):
    from tendermint_tpu.lens import analyze_run

    assert "journey_stall_budget_s" in DEFAULT_GATES
    # nodeB parks 120s of quorum wait on height 2: proposal + parts
    # arrive promptly after height 1's commit, then the precommit
    # quorum takes two minutes to assemble
    jk = T.journey_key
    slow = synth_node_events("nodeB", proposer=False, heights=(1,))
    t0 = 1.0 * US  # height 1's commit end
    slow += [
        {"name": "journey.proposal", "ph": "i", "ts": t0 + 0.1 * US, "tid": 1,
         "args": {"height": 2, "round": 0, "journey": jk(2, 0, "proposal", "nodeA")}},
        {"name": "journey.block_assembled", "ph": "X", "ts": t0 + 0.12 * US,
         "dur": 0.1 * US, "tid": 1,
         "args": {"height": 2, "round": 0, "parts": 2,
                  "journey": jk(2, 0, "block", "nodeA")}},
        {"name": "journey.quorum", "ph": "X", "ts": t0 + 0.3 * US,
         "dur": 120 * US, "tid": 1,
         "args": {"height": 2, "round": 0, "type": "precommit",
                  "journey": jk(2, 0, "precommit", "")}},
        {"name": "consensus.finalize_commit", "ph": "X", "ts": t0 + 120.5 * US,
         "dur": 0.2 * US, "tid": 1,
         "args": {"height": 2, "round": 0, "journey": jk(2, 0, "commit", "")}},
    ]
    run = write_run_dir(tmp_path, {
        "nodeA": synth_node_events("nodeA", proposer=True),
        "nodeB": slow,
    })
    report = analyze_run(run)
    gate = next(g for g in report["gates"] if g["name"] == "journey_stall")
    assert not gate["ok"]
    assert "nodeB" in gate["detail"] and "quorum" in gate["detail"]
    assert report["verdict"] == "fail"
    # budget override clears it
    report2 = analyze_run(run, gates={"journey_stall_budget_s": 500.0})
    gate2 = next(g for g in report2["gates"] if g["name"] == "journey_stall")
    assert gate2["ok"]
    # the gate is part of the default set (wired into every e2e verdict)
    assert {"liveness_stall", "journey_stall", "missing_series"} <= {
        g["name"] for g in report["gates"]
    }
    # per-node critical_path landed in the report, fleet digest too
    node_b = next(s for s in report["nodes"] if s["name"] == "nodeB")
    assert node_b["critical_path"]["heights"]
    assert report["fleet"]["critical_path"]["nodes"] == 2


# -------------------------------------------------------------------- CLI


def test_critical_path_cli_rc_paths(tmp_path, capsys):
    main = _tmlens_main()
    run = write_run_dir(tmp_path / "ok", {
        "nodeA": synth_node_events("nodeA", proposer=True),
        "nodeB": synth_node_events("nodeB", proposer=False, base_us=7 * US),
    })
    assert main(["critical-path", run]) == 0
    out = capsys.readouterr().out
    assert "nodeA" in out and "dominant" in out and "fleet:" in out
    # a tight budget trips the journey_stall condition -> rc 1
    assert main(["critical-path", run, "--budget", "0.01"]) == 1
    assert "JOURNEY STALL" in capsys.readouterr().err
    # --json emits machine-readable per-node paths
    assert main(["critical-path", run, "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert set(doc) == {"nodeA", "nodeB"}
    assert doc["nodeA"]["heights"]["2"]["stages"]["verify"] == pytest.approx(0.2) \
        or doc["nodeA"]["heights"][2]["stages"]["verify"] == pytest.approx(0.2)
    # usage / no-journey-spans paths -> rc 2
    assert main(["critical-path", str(tmp_path / "nope")]) == 2
    empty = tmp_path / "empty"
    (empty / "nodeA").mkdir(parents=True)
    (empty / "nodeA" / "metrics.txt").write_text("")
    assert main(["critical-path", str(empty)]) == 2
    assert main(["critical-path", run, "--bogus"]) == 2


def test_critical_path_cli_committed_fixture_smoke(capsys):
    """Tier-1 smoke against the COMMITTED fixture run-dir: the offline
    analysis path (trace load -> decomposition -> CLI) cannot silently
    rot while this passes."""
    main = _tmlens_main()
    assert os.path.isdir(FIXTURE_RUN), "committed fixture run-dir missing"
    assert main(["critical-path", FIXTURE_RUN]) == 0
    out = capsys.readouterr().out
    assert "nodeA: 3 heights" in out
    assert "nodeB: 3 heights" in out
    assert "fleet: dominant" in out
    # analyze over the same fixture folds critical_path into the report
    from tendermint_tpu.lens import analyze_run

    report = analyze_run(FIXTURE_RUN)
    assert report["fleet"]["critical_path"]["heights_covered"] == 3
    gate = next(g for g in report["gates"] if g["name"] == "journey_stall")
    assert gate["ok"], gate


# ----------------------------------------------------- dump_traces filter


def test_dump_traces_height_filter():
    """min_height/max_height keep only height-tagged events (plus
    thread-name metadata) — a one-block journey snapshot instead of the
    whole ring."""
    from tendermint_tpu.rpc import RPCEnvironment, build_routes

    routes = build_routes(RPCEnvironment(chain_id="journey-rpc", unsafe=True))
    was = T.enabled()
    T.set_enabled(True)
    T.clear()
    try:
        for h in (1, 2, 3):
            with T.span("consensus.finalize_commit", "consensus", height=h):
                pass
        with T.span("engine.coalesce", "engine"):  # no height arg
            pass
        res = routes["dump_traces"](min_height=2, max_height=2)
        evs = [e for e in res["trace"]["traceEvents"] if e.get("ph") != "M"]
        assert len(evs) == 1
        assert evs[0]["args"]["height"] == 2
        # string params (URI GET) parse like the other int routes
        res = routes["dump_traces"](min_height="3")
        evs = [e for e in res["trace"]["traceEvents"] if e.get("ph") != "M"]
        assert [e["args"]["height"] for e in evs] == [3]
        # unfiltered dump still ships everything
        res = routes["dump_traces"]()
        assert res["events"] >= 4
    finally:
        T.set_enabled(was)
        T.clear()
