"""The timed path broken underneath, for the control and for the tests
that must see `correct` come out false. Each fault is a function that
patches the program in place and returns the function that undoes it.

    lowered_verify   the control: breaks the configuration's guarantee "a block is
                     applied (a header stored) only after signatures of more than 2/3
                     of the voting power verified": a row the device refuses is
                     accepted all the same if it is well formed (s < L, A and R on
                     the curve), the curve equation left out
    half_batch       the second half of every caller's batch is left out of
                     verification and taken as valid
    state_unchanged  a step returns its state unchanged: apply_block hands back the
                     state it was given; the light store saves nothing
    altered_answer   an answer altered where it is produced: the application's app
                     hash; the header the light client returns
"""

from __future__ import annotations

from benchmark import reference as ref


def _patch(obj, name, new):
    old = getattr(obj, name)
    setattr(obj, name, new)
    return lambda: setattr(obj, name, old)


def _verdicts(alter):
    """Wrap the engine's collect thunk: every caller's job of a launch
    gets alter(its verdicts, its rows) in place of its verdicts."""
    from tendermint_tpu.ops.engine import VerifyEngine

    original = VerifyEngine._dispatch_group

    def dispatch_group(self, group, seq=0):
        thunk, path = original(self, group, seq)

        def collect():
            bools, out, lo = list(thunk()), [], 0
            for j in group:
                out += alter(bools[lo: lo + j.n], list(zip(j.pks, j.msgs, j.sigs)))
                lo += j.n
            return out

        return collect, path

    return _patch(VerifyEngine, "_dispatch_group", dispatch_group)


def lowered_verify():
    return _verdicts(lambda bools, rows: [
        ok or ref.verify_lowered(bytes(pk), bytes(msg), bytes(sig))
        for ok, (pk, msg, sig) in zip(bools, rows)])


def half_batch():
    return _verdicts(lambda bools, rows: bools[: len(bools) // 2]
                     + [True] * (len(bools) - len(bools) // 2))


def state_unchanged():
    from tendermint_tpu.light.store import MemLightStore
    from tendermint_tpu.state import BlockExecutor

    undo = [_patch(BlockExecutor, "apply_block", lambda self, state, block_id, block: state),
            _patch(MemLightStore, "save_light_block", lambda self, lb: None)]
    return lambda: [u() for u in undo]


def altered_answer():
    from tendermint_tpu.abci.kvstore import KVStoreApplication
    from tendermint_tpu.light.client import LightClient

    verify_at = LightClient.verify_light_block_at_height

    def another_header(self, height, now=None):
        verify_at(self, height, now)
        return self.primary.light_block(height - 1)

    undo = [_patch(KVStoreApplication, "_compute_app_hash", lambda self: b"\x01" * 8),
            _patch(LightClient, "verify_light_block_at_height", another_header)]
    return lambda: [u() for u in undo]


FAULTS = {f.__name__: f for f in (lowered_verify, half_batch, state_unchanged, altered_answer)}
