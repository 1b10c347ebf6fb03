"""The reference against the program's own encoders and oracle, on the
CPU: they were written apart and have to agree byte for byte."""

import hashlib

from benchmark import chain as chainlib
from benchmark import reference as ref


def test_wire_formats_and_keys_agree_with_the_program():
    from tendermint_tpu.crypto import ed25519_ref as oracle

    cfg = dict(validators=4, blocks=3, txs_per_block=4, chain_id="t", voting_power=10)
    chain = chainlib.build(cfg, 2**31 + 7)
    for h in (1, 2, 3):
        block = chain.block_store.load_block(h)
        commit = chain.block_store.load_seen_commit(h)
        assert ref.header_hash(chainlib.header_values(block.header)) == block.hash()
        sigs, msgs = chainlib.commit_values(chain, commit)
        assert msgs[1] == commit.vote_sign_bytes("t", 1)
        assert ref.commit_verdict(chain.pubkeys, chain.powers, sigs, msgs, 2, 3, False) == (True, 4)
    assert ref.kvstore_app_hash(4 * 3) == chain.state.app_hash
    seed = hashlib.sha256(b"k").digest()
    assert ref.public_key(seed) == oracle.gen_privkey(seed)[32:]
    assert ref.signer(seed)(b"m") == ref.sign_plain(seed, b"m") == oracle.sign(
        oracle.gen_privkey(seed), b"m")


def test_acceptance_is_zip215():
    from tendermint_tpu.crypto import ed25519_ref as oracle

    seed = hashlib.sha256(b"k").digest()
    pk, sig = ref.public_key(seed), ref.sign_plain(seed, b"m")
    assert ref.verify(pk, b"m", sig) and ref.verify_plain(pk, b"m", sig)
    assert not ref.verify(pk, b"n", sig)
    flipped = chainlib.flip_s(sig)
    assert not ref.verify(pk, b"m", flipped) and ref.verify_lowered(pk, b"m", flipped)
    s_high = sig[:32] + (int.from_bytes(sig[32:], "little") + ref.L).to_bytes(32, "little")
    assert not ref.verify(pk, b"m", s_high) and not ref.verify_lowered(pk, b"m", s_high)
    edge = oracle.compress(oracle.IDENTITY) + b"\x00" * 32  # small-order A, identity R, s = 0
    for small in oracle.small_order_points():
        assert ref.verify(small, b"m", edge) == oracle.verify(small, b"m", edge, zip215=True)


def test_commit_rules():
    pks, powers = [b"a", b"b", b"c", b"d"], [10] * 4
    good = lambda pk, msg, sig: sig == b"ok"  # noqa: E731
    verdict = lambda sigs, num, den, early: ref.commit_verdict(  # noqa: E731
        pks, powers, sigs, [b""] * 4, num, den, early, good)
    assert verdict([b"ok"] * 4, 2, 3, True) == (True, 3)  # stops past 2/3
    assert verdict([b"ok"] * 4, 2, 3, False) == (True, 4)
    assert verdict([b"ok", b"ok", None, None], 2, 3, True) == (False, 2)  # 20 of 40
    assert verdict([b"ok", b"bad", b"ok", b"ok"], 2, 3, True) == (False, 2)
    assert verdict([b"ok", b"ok", b"ok", b"bad"], 2, 3, True) == (True, 3)  # never reached
    assert verdict([b"ok", b"ok", b"ok", b"bad"], 2, 3, False) == (False, 4)
    assert verdict([b"ok", b"ok", None, None], 1, 3, True) == (True, 2)  # trusting
