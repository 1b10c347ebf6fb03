"""`StateStore.save` writes what it wrote before its sets were encoded
in one pass (`ValidatorSet.to_bytes`): every document, byte for byte,
against one built here with `to_proto().encode()`, over a 150-validator
state whose set loses and gains a key at most blocks. With it the
`state.save` span's `rows` and `rows_kept`, and the `validator_row`
memo counter.
"""

from __future__ import annotations

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from helpers import make_block_id, make_genesis_doc, make_keys  # noqa: E402

from tendermint_tpu import trace  # noqa: E402
from tendermint_tpu.crypto.ed25519 import Ed25519PrivKey  # noqa: E402
from tendermint_tpu.metrics import hash_metrics  # noqa: E402
from tendermint_tpu.state import StateStore, make_genesis_state  # noqa: E402
from tendermint_tpu.state.store import (  # noqa: E402
    KEY_PARAMS, KEY_STATE, KEY_VALIDATORS, _hkey, state_to_json)
from tendermint_tpu.store.kv import MemDB  # noqa: E402
from tendermint_tpu.types.block import Header  # noqa: E402
from tendermint_tpu.types.genesis import _b64, _params_to_json  # noqa: E402
from tendermint_tpu.types.validator_set import Validator  # noqa: E402
from tendermint_tpu.utils.tmtime import Time  # noqa: E402

BLOCKS = 40
SETS = ("validators", "next_validators", "last_validators")


class RecordingDB(MemDB):
    def __init__(self):
        super().__init__()
        self.written: list[tuple[bytes, bytes]] = []

    def set(self, key: bytes, value: bytes) -> None:
        self.written.append((bytes(key), bytes(value)))
        super().set(key, value)


@pytest.fixture
def traced():
    was = trace.enabled()
    trace.set_enabled(True)
    trace.clear()
    yield
    trace.set_enabled(was)
    trace.clear()


def by_codec(vs) -> str:
    return _b64(vs.to_proto().encode())


def expected_writes(state) -> list[tuple[bytes, bytes]]:
    """What `StateStore.save` writes for `state` past genesis, in order,
    every set encoded through `pb`."""
    next_height = state.last_block_height + 1
    changed = min(state.last_height_validators_changed, next_height + 1)
    vals = {"last_height_changed": changed}
    if changed == next_height + 1:
        vals["validator_set"] = by_codec(state.next_validators)
    params = {"last_height_changed": state.last_height_consensus_params_changed}
    if next_height == state.last_height_consensus_params_changed:
        params["params"] = _params_to_json(state.consensus_params)
    doc = state_to_json(state)
    for name in SETS:
        doc[name] = by_codec(getattr(state, name))
    return [(_hkey(KEY_VALIDATORS, next_height + 1), json.dumps(vals).encode()),
            (_hkey(KEY_PARAMS, next_height), json.dumps(params).encode()),
            (KEY_STATE, json.dumps(doc).encode())]


def row_counts() -> dict[str, float]:
    return {labels["event"]: v for _, labels, v in hash_metrics().cache_events.samples()
            if labels["site"] == "validator_row"}


def last_save_span() -> dict:
    return [ev for ev in trace.export()["traceEvents"]
            if ev.get("ph") == "X" and ev["name"] == "state.save"][-1]["args"]


def test_every_document_save_writes_is_the_codecs_and_the_span_counts_its_rows(traced):
    keys = make_keys(150)
    state = make_genesis_state(make_genesis_doc(keys, chain_id="store-encode"))
    db = RecordingDB()
    store = StateStore(db)
    store.save(state)
    joiners = iter(Ed25519PrivKey.generate(bytes([7, i]) * 16).pub_key() for i in range(BLOCKS))
    for h in range(1, BLOCKS + 1):
        # one key out and one in, except at every fifth block
        changes = []
        if h % 5:
            leaver = min(state.next_validators.validators, key=lambda v: v.address)
            changes = [Validator(leaver.address, leaver.pub_key, 0), Validator.new(next(joiners), 10)]
        header = Header(chain_id=state.chain_id, height=h,
                        time=Time.from_unix_ns(1_700_000_000 * 10**9 + h * 10**9))
        state = state.update(make_block_id(bytes([h]) * 32), header, b"", None, changes)
        db.written.clear()
        before = row_counts()
        store.save(state)
        after = row_counts()

        assert db.written == expected_writes(state), h
        args = last_save_span()
        sets = [getattr(state, name) for name in SETS]
        if args["full_sets_written"]:
            sets.append(state.next_validators)
        assert args["rows"] == sum(len(vs.validators) + (vs.proposer is not None) for vs in sets)
        # the joiner is the one row not seen before, once: its second encode is kept
        assert args["rows"] - args["rows_kept"] == (1 if changes else 0), h
        assert after.get("hit", 0) - before.get("hit", 0) == args["rows_kept"]
        assert after.get("miss", 0) - before.get("miss", 0) == args["rows"] - args["rows_kept"]

        loaded = store.load()
        for name in SETS:
            assert getattr(loaded, name).to_proto().encode() == getattr(state, name).to_proto().encode()
        for height, vs in ((h, state.last_validators), (h + 1, state.validators),
                           (h + 2, state.next_validators)):
            got = store.load_validators(height)
            assert got.validators == vs.validators and got.proposer == vs.proposer, (h, height)
