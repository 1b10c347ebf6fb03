"""Out-of-process ABCI: proto roundtrips, socket server/client,
and a node committing blocks against an app in a SEPARATE PROCESS
(ref: abci/client/socket_client.go, abci/server/socket_server.go,
test/app/test.sh's kvstore-over-socket flow)."""

from __future__ import annotations

import os
import subprocess
import sys
import threading
import time

import pytest

from tendermint_tpu.abci import proto as apb
from tendermint_tpu.abci import types as abci
from tendermint_tpu.abci.kvstore import KVStoreApplication
from tendermint_tpu.abci.socket import SocketClient, SocketServer


def test_request_response_proto_roundtrip():
    req = abci.RequestFinalizeBlock(
        txs=[b"a=1", b"b=2"],
        decided_last_commit=abci.CommitInfo(
            round=2,
            votes=[abci.VoteInfo(validator=abci.Validator(address=b"\x01" * 20, power=10), signed_last_block=True)],
        ),
        misbehavior=[
            abci.Misbehavior(
                type=abci.MISBEHAVIOR_DUPLICATE_VOTE,
                validator=abci.Validator(address=b"\x02" * 20, power=5),
                height=7,
                time_ns=1_700_000_000 * 10**9 + 123,
                total_voting_power=30,
            )
        ],
        hash=b"\xaa" * 32,
        height=8,
        time_ns=1_700_000_001 * 10**9,
        next_validators_hash=b"\xbb" * 32,
        proposer_address=b"\x03" * 20,
    )
    pb = apb.request_to_pb("finalize_block", req)
    back_method, back = apb.request_from_pb(apb.RequestPB.decode(pb.encode()))
    assert back_method == "finalize_block"
    assert back == req

    res = abci.ResponseFinalizeBlock(
        events=[abci.Event(type="commit", attributes=[abci.EventAttribute(key="k", value="v", index=True)])],
        tx_results=[abci.ExecTxResult(code=0, data=b"ok", gas_used=3)],
        validator_updates=[abci.ValidatorUpdate(pub_key_type="ed25519", pub_key_bytes=b"\x04" * 32, power=9)],
        app_hash=b"\xcc" * 32,
    )
    rpb = apb.response_to_pb("finalize_block", res)
    kind, rback = apb.response_from_pb(apb.ResponsePB.decode(rpb.encode()))
    assert kind == "finalize_block"
    assert rback == res


def test_prepare_proposal_txs_to_tx_records():
    res = abci.ResponsePrepareProposal(txs=[b"x", b"y"])
    pb = apb.response_to_pb("prepare_proposal", res)
    assert all(r.action == apb.TXRECORD_UNMODIFIED for r in pb.prepare_proposal.tx_records)
    _, back = apb.response_from_pb(apb.ResponsePB.decode(pb.encode()))
    assert back.txs == [b"x", b"y"]


def test_exception_response_raises():
    pb = apb.ResponsePB(exception=apb.ResponseExceptionPB(error="boom"))
    with pytest.raises(apb.ABCIRemoteError, match="boom"):
        apb.response_from_pb(pb)


@pytest.fixture()
def socket_pair():
    app = KVStoreApplication()
    srv = SocketServer(app, "tcp://127.0.0.1:0")
    srv.start()
    client = SocketClient(srv.listen_addr, timeout=10.0)
    client.start()
    yield app, srv, client
    client.stop()
    srv.stop()


def test_socket_roundtrip_kvstore(socket_pair):
    app, srv, client = socket_pair
    info = client.info(abci.RequestInfo())
    assert info.last_block_height == 0
    res = client.check_tx(abci.RequestCheckTx(tx=b"k=v", type=0))
    assert res.is_ok
    f = client.finalize_block(
        abci.RequestFinalizeBlock(txs=[b"k=v"], height=1, hash=b"\x01" * 32)
    )
    assert len(f.tx_results) == 1 and f.tx_results[0].is_ok
    client.commit()
    q = client.query(abci.RequestQuery(path="/store", data=b"k"))
    assert q.value == b"v"


def test_secondary_connection_keeps_pending_block(socket_pair):
    """A second client (debug/monitoring tool) connecting while the
    primary has a block in flight must NOT clear the app's pending
    FinalizeBlock effects — only the FIRST connection triggers
    reload_committed."""
    app, srv, client = socket_pair
    f = client.finalize_block(
        abci.RequestFinalizeBlock(txs=[b"pend=1"], height=1, hash=b"\x02" * 32)
    )
    assert f.tx_results[0].is_ok
    # block in flight (no Commit yet); a monitoring client attaches
    client2 = SocketClient(srv.listen_addr, timeout=10.0)
    client2.start()
    try:
        assert client2.echo("probe") == "probe"
        # the pending block must survive the secondary accept
        client.commit()
        q = client.query(abci.RequestQuery(path="/store", data=b"pend"))
        assert q.value == b"1"
        assert client.info(abci.RequestInfo()).last_block_height == 1
    finally:
        client2.stop()


def test_reload_after_crash_mid_first_block():
    """Crash between FinalizeBlock(1) and Commit with NO prior persisted
    state: reload must reset in-memory height/size/app_hash to genesis,
    not keep reporting the uncommitted height whose effects were
    discarded."""
    app = KVStoreApplication()
    app.finalize_block(abci.RequestFinalizeBlock(txs=[b"x=1"], height=1))
    app.reload_committed()  # crash + reconnect before any Commit
    info = app.info(abci.RequestInfo())
    assert info.last_block_height == 0
    assert info.last_block_app_hash in (b"", None)
    # replaying block 1 now applies cleanly
    res = app.finalize_block(abci.RequestFinalizeBlock(txs=[b"x=1"], height=1))
    assert res.tx_results[0].is_ok
    app.commit()
    assert app.info(abci.RequestInfo()).last_block_height == 1
    assert app.query(abci.RequestQuery(data=b"x")).value == b"1"


def test_socket_pipelining(socket_pair):
    _, _, client = socket_pair
    # many concurrent callers; FIFO matching must never cross wires
    results: dict[int, bytes] = {}
    errs: list = []

    def worker(i: int):
        try:
            r = client.echo(f"m{i}")
            results[i] = r
        except Exception as e:  # pragma: no cover
            errs.append(e)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(32)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    assert results == {i: f"m{i}" for i in range(32)}


def test_socket_server_exception_propagates():
    class BadApp(abci.BaseApplication):
        def query(self, req):
            raise RuntimeError("query exploded")

    srv = SocketServer(BadApp(), "tcp://127.0.0.1:0")
    srv.start()
    client = SocketClient(srv.listen_addr, timeout=10.0)
    client.start()
    try:
        with pytest.raises(apb.ABCIRemoteError, match="query exploded"):
            client.query(abci.RequestQuery(path="/x"))
        # connection survives an app exception
        assert client.echo("still-alive") == "still-alive"
    finally:
        client.stop()
        srv.stop()


def test_node_with_external_app_process(tmp_path):
    """VERDICT item 4 'done' criterion: a node commits blocks with the
    app running in a separate OS process, dialed via proxy_app."""
    from tendermint_tpu.cli import main as cli_main
    from tendermint_tpu.config import load_config
    from tendermint_tpu.node import Node

    sock_path = str(tmp_path / "abci.sock")
    addr = f"unix://{sock_path}"
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.Popen(
        [sys.executable, "-m", "tendermint_tpu.abci.socket", "--addr", addr],
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env=env,
        stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT,
    )
    try:
        deadline = time.monotonic() + 30
        while not os.path.exists(sock_path):
            assert time.monotonic() < deadline, "external app never listened"
            assert proc.poll() is None, proc.stdout.read().decode()
            time.sleep(0.05)

        home = str(tmp_path / "node")
        assert cli_main(["--home", home, "init", "validator", "--chain-id", "ext-app-chain"]) == 0
        cfg = load_config(home)
        cfg.base.proxy_app = addr
        cfg.p2p.laddr = "tcp://127.0.0.1:0"
        cfg.rpc.laddr = "tcp://127.0.0.1:0"
        cfg.base.db_backend = "memdb"
        node = Node(cfg)
        node.start()
        try:
            # commit a tx through the external app
            node.mempool.check_tx(b"extkey=extval")
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline and node.consensus.rs.height < 3:
                time.sleep(0.1)
            assert node.consensus.rs.height >= 3, "no blocks against external app"
            q = node.app_client.query(abci.RequestQuery(path="/store", data=b"extkey"))
            assert q.value == b"extval"
        finally:
            node.stop()
    finally:
        proc.terminate()
        proc.wait(timeout=10)


# --------------------------------------------- CheckTx wire fast path


def test_check_tx_fast_codec_byte_identical_to_generic():
    """The hand-rolled CheckTx encoders/decoders (the flood hot path)
    must emit the generic reflection codec's exact bytes and decode
    its output exactly — including proto3 default skipping, negative
    int64s, and fall-through on non-CheckTx frames."""
    reqs = [
        abci.RequestCheckTx(tx=b"", type=0),
        abci.RequestCheckTx(tx=b"k=v", type=0),
        abci.RequestCheckTx(tx=b"x" * 5000, type=1),
    ]
    for req in reqs:
        fast = apb.encode_check_tx_request(req)
        generic = apb.request_to_pb("check_tx", req).encode()
        assert fast == generic
        assert apb.try_decode_check_tx_request(generic) == req
    resps = [
        abci.ResponseCheckTx(),
        abci.ResponseCheckTx(code=3, data=b"d", gas_wanted=77, codespace="cs",
                             sender="s", priority=12),
        abci.ResponseCheckTx(gas_wanted=-1, priority=-5),
    ]
    for res in resps:
        fast = apb.encode_check_tx_response(res)
        generic = apb.response_to_pb("check_tx", res).encode()
        assert fast == generic
        assert apb.try_decode_check_tx_response(generic) == res
    # non-CheckTx frames fall through to the generic decoder
    assert apb.try_decode_check_tx_request(
        apb.request_to_pb("echo", "hi").encode()) is None
    assert apb.try_decode_check_tx_response(
        apb.ResponsePB(exception=apb.ResponseExceptionPB(error="x")).encode()) is None
    # corrupt frames (inner length overrunning the frame) must NOT be
    # silently truncated — fall through so the generic decoder raises
    good_req = apb.encode_check_tx_request(abci.RequestCheckTx(tx=b"abcdef"))
    assert apb.try_decode_check_tx_request(good_req[:-2]) is None
    good_res = apb.encode_check_tx_response(abci.ResponseCheckTx(data=b"abcdef"))
    assert apb.try_decode_check_tx_response(good_res[:-2]) is None
    # consistent outer size but inner field length overruns the frame
    evil = b"\x3a\x05" + b"\x0a\x0a" + b"abc"  # tx declares 10 bytes, has 3
    assert apb.try_decode_check_tx_request(evil) is None


def test_socket_check_tx_batch_pipelined(socket_pair):
    """check_tx_batch pipelines N requests (one write burst, FIFO
    response matching) and returns responses in request order,
    identical to N sequential calls."""
    _, _, client = socket_pair
    reqs = [abci.RequestCheckTx(tx=b"b%d=%d" % (i, i), type=0) for i in range(300)]
    batched = client.check_tx_batch(reqs)
    sequential = [client.check_tx(r) for r in reqs]
    assert batched == sequential
    assert all(r.is_ok for r in batched)
    # interleaves safely with other traffic on the same connection
    assert client.echo("after-batch") == "after-batch"


def test_socket_check_tx_batch_remote_error(socket_pair):
    """An app exception inside a pipelined batch fails that request
    with ABCIRemoteError and leaves the connection usable."""
    app, _, client = socket_pair
    orig = app.check_tx

    def flaky(req):
        if req.tx == b"boom":
            raise RuntimeError("checktx exploded")
        return orig(req)

    app.check_tx = flaky
    try:
        reqs = [abci.RequestCheckTx(tx=t) for t in (b"ok1", b"boom", b"ok2")]
        slots = client._submit_batch("check_tx", reqs)
        results = []
        for s in slots:
            try:
                results.append(client._await("check_tx", s))
            except apb.ABCIRemoteError as e:
                results.append(e)
        assert results[0].is_ok and results[2].is_ok
        assert isinstance(results[1], apb.ABCIRemoteError)
        assert client.echo("alive") == "alive"
    finally:
        app.check_tx = orig
