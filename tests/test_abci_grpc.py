"""gRPC ABCI transport: roundtrip, concurrency, error surface, and a
node committing blocks against a gRPC app in a separate process
(ref: abci/client/grpc_client.go, abci/server/grpc_server.go)."""

from __future__ import annotations

import os
import subprocess
import sys
import threading
import time

import pytest

grpc = pytest.importorskip("grpc")

from tendermint_tpu.abci import proto as apb
from tendermint_tpu.abci import types as abci
from tendermint_tpu.abci.grpc import GRPCClient, GRPCServer
from tendermint_tpu.abci.kvstore import KVStoreApplication


@pytest.fixture()
def grpc_pair():
    app = KVStoreApplication()
    srv = GRPCServer(app, "127.0.0.1:0")
    srv.start()
    client = GRPCClient(srv.listen_addr, timeout=10.0)
    client.start()
    yield app, srv, client
    client.stop()
    srv.stop()


def test_grpc_roundtrip_kvstore(grpc_pair):
    app, srv, client = grpc_pair
    assert client.echo("hello") == "hello"
    client.flush()
    info = client.info(abci.RequestInfo())
    assert info.last_block_height == 0
    res = client.check_tx(abci.RequestCheckTx(tx=b"gk=gv", type=0))
    assert res.is_ok
    f = client.finalize_block(
        abci.RequestFinalizeBlock(txs=[b"gk=gv"], height=1, hash=b"\x01" * 32)
    )
    assert len(f.tx_results) == 1 and f.tx_results[0].is_ok
    client.commit()
    q = client.query(abci.RequestQuery(path="/store", data=b"gk"))
    assert q.value == b"gv"


def test_grpc_concurrent_callers(grpc_pair):
    _, _, client = grpc_pair
    results: dict[int, str] = {}
    errs: list = []

    def worker(i: int):
        try:
            results[i] = client.echo(f"g{i}")
        except Exception as e:  # pragma: no cover
            errs.append(e)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(32)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    assert results == {i: f"g{i}" for i in range(32)}


def test_grpc_app_exception_propagates():
    class BadApp(abci.BaseApplication):
        def query(self, req):
            raise RuntimeError("grpc query exploded")

    srv = GRPCServer(BadApp(), "127.0.0.1:0")
    srv.start()
    client = GRPCClient(srv.listen_addr, timeout=10.0)
    client.start()
    try:
        with pytest.raises(apb.ABCIRemoteError, match="grpc query exploded"):
            client.query(abci.RequestQuery(path="/x"))
        # channel survives an app exception
        assert client.echo("still-alive") == "still-alive"
    finally:
        client.stop()
        srv.stop()


def test_node_with_external_grpc_app(tmp_path):
    """A node commits blocks with the app in a separate OS process,
    dialed via proxy_app = grpc:// (the reference's grpc deployment
    mode, test/e2e manifest abci_protocol = "grpc")."""
    from tendermint_tpu.cli import main as cli_main
    from tendermint_tpu.config import load_config
    from tendermint_tpu.node import Node

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.Popen(
        [sys.executable, "-m", "tendermint_tpu.abci.socket",
         "--addr", "grpc://127.0.0.1:0"],
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env=env,
        stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT,
        text=True,
    )
    try:
        line = proc.stdout.readline()
        assert "listening on" in line, line
        addr = line.strip().rsplit(" ", 1)[-1]

        home = str(tmp_path / "node")
        assert cli_main(["--home", home, "init", "validator",
                         "--chain-id", "grpc-app-chain"]) == 0
        cfg = load_config(home)
        cfg.base.proxy_app = addr
        cfg.p2p.laddr = "tcp://127.0.0.1:0"
        cfg.rpc.laddr = "tcp://127.0.0.1:0"
        cfg.base.db_backend = "memdb"
        node = Node(cfg)
        node.start()
        try:
            node.mempool.check_tx(b"grpckey=grpcval")
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline and node.consensus.rs.height < 3:
                time.sleep(0.1)
            assert node.consensus.rs.height >= 3, "no blocks against grpc app"
            q = node.app_client.query(abci.RequestQuery(path="/store", data=b"grpckey"))
            assert q.value == b"grpcval"
        finally:
            node.stop()
    finally:
        proc.terminate()
        proc.wait(timeout=10)
