"""Light proxy tests: verifying RPC façade over a running node
(ref: light/proxy/proxy.go, light/rpc/client.go)."""

from __future__ import annotations

import os
import sys
import time

import pytest

sys.path.insert(0, os.path.dirname(__file__))

from test_consensus import fast_params

from tendermint_tpu.cli import main as cli_main
from tendermint_tpu.config import load_config
from tendermint_tpu.light import LightClient, TrustOptions
from tendermint_tpu.light.http_provider import HTTPProvider
from tendermint_tpu.light.proxy import LightProxy
from tendermint_tpu.node import Node
from tendermint_tpu.rpc.client import HTTPClient, RPCClientError
from tendermint_tpu.types.genesis import GenesisDoc


@pytest.fixture(scope="module")
def node(tmp_path_factory):
    out = str(tmp_path_factory.mktemp("lpnet"))
    assert cli_main(["testnet", "--validators", "1", "--output", out,
                     "--chain-id", "lp-chain", "--starting-port", "0"]) == 0
    gp = os.path.join(out, "node0", "config", "genesis.json")
    gd = GenesisDoc.from_file(gp)
    gd.consensus_params = fast_params()
    gd.save_as(gp)
    cfg = load_config(os.path.join(out, "node0"))
    cfg.p2p.laddr = "tcp://127.0.0.1:0"
    cfg.rpc.laddr = "tcp://127.0.0.1:0"
    n = Node(cfg)
    n.start()
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline and n.block_store.height() < 4:
        time.sleep(0.05)
    assert n.block_store.height() >= 4
    yield n
    n.stop()


@pytest.fixture(scope="module")
def proxy(node):
    host, port = node.rpc_address
    primary_url = f"http://{host}:{port}"
    primary = HTTPProvider("lp-chain", primary_url)
    lb1 = primary.light_block(1)
    opts = TrustOptions(period_ns=3600 * 10**9, height=1, hash=lb1.signed_header.hash())
    lc = LightClient("lp-chain", opts, primary)
    p = LightProxy(lc, primary_url)
    p.start()
    yield p
    p.stop()


def _client(proxy) -> HTTPClient:
    host, port = proxy.address
    return HTTPClient(f"http://{host}:{port}")


def test_proxy_block_verified(proxy, node):
    c = _client(proxy)
    res = c.call("block", height="2")
    direct = HTTPClient(f"http://{node.rpc_address[0]}:{node.rpc_address[1]}").call("block", height="2")
    assert res["block_id"]["hash"] == direct["block_id"]["hash"]


def test_proxy_header_and_validators(proxy):
    c = _client(proxy)
    h = c.call("header", height="3")
    assert h["header"]["height"] == "3" and h["header"]["chain_id"] == "lp-chain"
    v = c.call("validators", height="3")
    assert v["count"] == "1" and len(v["validators"]) == 1


def test_proxy_status_reports_verified_head(proxy):
    c = _client(proxy)
    res = c.call("status")
    assert int(res["sync_info"]["latest_block_height"]) >= 2
    assert res["node_info"]  # forwarded from primary


def test_proxy_commit_and_passthrough(proxy):
    c = _client(proxy)
    res = c.call("commit", height="2")
    assert res["signed_header"]["commit"]["height"] == "2"
    assert c.call("health") == {}


def test_proxy_requires_height(proxy):
    c = _client(proxy)
    with pytest.raises(RPCClientError, match="height"):
        c.call("block")


def test_proxy_light_batch_serves_verified_store(proxy, node):
    """light_batch comes from the proxy's OWN verified store — header,
    commit, and validator set the light client already checked — in
    one round trip (tmproof gateway)."""
    c = _client(proxy)
    res = c.call("light_batch", height="2")
    direct = HTTPClient(
        f"http://{node.rpc_address[0]}:{node.rpc_address[1]}"
    ).call("commit", height="2")
    assert res["signed_header"]["header"]["height"] == "2"
    assert res["canonical"] is True
    assert (
        res["signed_header"]["commit"]["block_id"]["hash"]
        == direct["signed_header"]["commit"]["block_id"]["hash"]
    )
    assert int(res["total_validators"]) == len(res["validators"]) == 1


def test_proxy_light_batch_refuses_past_verified_head(proxy):
    """A verifying proxy must not relay heights it cannot verify: a
    request past the (updated) verified head is an error, never a
    pass-through."""
    c = _client(proxy)
    with pytest.raises(RPCClientError, match="past the verified head"):
        c.call("light_batch", height=str(10**6))


def test_proxy_proofs_batch_verifies_before_relaying(proxy, node, monkeypatch):
    """proofs_batch relays the primary's multiproof only after it
    reconstructs the LIGHT-VERIFIED header's data_hash; a primary that
    tampers one shared node (or one tx byte) is rejected."""
    import base64
    import hashlib

    from tendermint_tpu.rpc.core import multiproof_from_json

    # commit a burst of txs so ONE height carries a multi-leaf tree
    # (the index-substitution case below needs >= 2 provable indices):
    # they are admitted under the mempool's lock, which the proposer's
    # reap takes too, so that a reap sees all three or none
    direct = HTTPClient(f"http://{node.rpc_address[0]}:{node.rpc_address[1]}")
    node.mempool.lock()
    try:
        for i in range(3):
            assert node.mempool.check_tx(f"lpk{i}=lpv{i}".encode()).is_ok
    finally:
        node.mempool.unlock()
    height = None
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline and height is None:
        head = int(direct.call("status")["sync_info"]["latest_block_height"])
        for h in range(head, 0, -1):
            blk = direct.call("block", height=h)
            if len((blk["block"]["data"] or {}).get("txs") or []) >= 2:
                height = h
                break
        time.sleep(0.2)
    assert height is not None, "tx burst never landed >= 2 txs in one block"

    c = _client(proxy)
    out = c.call("proofs_batch", height=str(height), indices=[0])
    mp = multiproof_from_json(out["multiproof"])
    txs = [base64.b64decode(t) for t in out["txs"]]
    assert mp.verify(
        bytes.fromhex(out["root"]), [hashlib.sha256(tx).digest() for tx in txs]
    )

    real = proxy.primary.call

    def tampering_call(method, **params):
        resp = real(method, **params)
        if method == "proofs_batch":
            resp["txs"] = [base64.b64encode(b"spoofed").decode()]
        return resp

    monkeypatch.setattr(proxy.primary, "call", tampering_call)
    with pytest.raises(RPCClientError, match="multiproof does not verify"):
        c.call("proofs_batch", height=str(height), indices=[0])

    # index substitution: a VALIDLY-proven but different index set is
    # still an attack — the primary answers the client's [0] with its
    # own genuine proof for [1]
    def substituting_call(method, **params):
        if method == "proofs_batch":
            return real(method, **dict(params, indices=[1]))
        return real(method, **params)

    monkeypatch.setattr(proxy.primary, "call", substituting_call)
    with pytest.raises(RPCClientError, match="different indices"):
        c.call("proofs_batch", height=str(height), indices=[0])
    monkeypatch.setattr(proxy.primary, "call", real)


def test_proxy_rejects_spoofed_block(proxy, node, monkeypatch):
    """A primary that self-reports the verified hash but returns a
    tampered body must be rejected — the proxy recomputes hashes
    (ref: light/rpc/client.go Block)."""
    real = proxy.primary.call

    def spoofing_call(method, **params):
        res = real(method, **params)
        if method == "block":
            res["block"]["data"]["txs"] = ["c3Bvb2ZlZA=="]  # injected tx
        return res

    monkeypatch.setattr(proxy.primary, "call", spoofing_call)
    c = _client(proxy)
    with pytest.raises(RPCClientError, match="data_hash|verification failed"):
        c.call("block", height="2")
    monkeypatch.setattr(proxy.primary, "call", real)


def test_proxy_rejects_wrong_header(proxy, node, monkeypatch):
    real = proxy.primary.call

    def spoofing_call(method, **params):
        res = real(method, **params)
        if method == "block":
            res["block"]["header"]["app_hash"] = "ff" * 32  # forged header field
        return res

    monkeypatch.setattr(proxy.primary, "call", spoofing_call)
    c = _client(proxy)
    with pytest.raises(RPCClientError, match="!= verified|verification failed"):
        c.call("block", height="3")
    monkeypatch.setattr(proxy.primary, "call", real)
