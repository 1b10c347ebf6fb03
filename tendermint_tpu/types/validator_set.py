"""Validator and ValidatorSet (ref: types/validator.go, types/validator_set.go).

The proposer-priority rotation and the deterministic update algorithm are
consensus-critical: every node must compute the identical proposer for
every (height, round) and the identical post-update set, so the arithmetic
(int64 clipping, centering, rescaling) matches the reference exactly
(validator_set.go:116 IncrementProposerPriority, :584 updateWithChangeSet).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field

from ..crypto import PubKey, encoding
from ..crypto.ed25519 import Ed25519PubKey
from ..crypto.merkle import hash_from_byte_slices
from ..metrics import hash_metrics
from ..proto import messages as pb
from ..proto import wire

# ref: types/validator_set.go:25 — cap so priority arithmetic can't overflow.
MAX_TOTAL_VOTING_POWER = (2**63 - 1) // 8
# ref: types/validator_set.go:30 — priority window = 2 * total power.
PRIORITY_WINDOW_SIZE_FACTOR = 2
# ref: types/vote_set.go:19 — DoS bound on set size; commits by a larger
# set fail validation (validator_set.go:68 commentary).
MAX_VOTES_COUNT = 10000

_INT64_MAX = 2**63 - 1
_INT64_MIN = -(2**63)

# the tags ValidatorSet.to_bytes writes: pb.ValidatorSet's validators (1),
# proposer (2) and total_voting_power (3), pb.Validator's proposer_priority (4)
_ROW_TAG = b"\x0a"
_PROPOSER_TAG = b"\x12"
_TOTAL_TAG = b"\x18"
_PRIORITY_TAG = b"\x20"
_uvarint = wire.encode_varint


def _clip64(v: int) -> int:
    """int64 saturating clamp (ref: safeAddClip/safeSubClip, types/utils.go)."""
    if v > _INT64_MAX:
        return _INT64_MAX
    if v < _INT64_MIN:
        return _INT64_MIN
    return v


class NotEnoughVotingPowerError(Exception):
    """ref: ErrNotEnoughVotingPowerSigned (types/validator_set.go)."""

    def __init__(self, got: int, needed: int):
        self.got = got
        self.needed = needed
        super().__init__(f"invalid commit -- insufficient voting power: got {got}, needed more than {needed}")


@dataclass
class Validator:
    address: bytes
    pub_key: PubKey
    voting_power: int
    proposer_priority: int = 0
    # Guarded memo of the SimpleValidator leaf encoding: the cached
    # tuple re-checks (pub_key identity, voting_power) on every read,
    # so direct field writes can never serve a stale encode. Carried
    # through copy() — priorities change every height but the leaf
    # encoding does not, so the encode survives the per-block
    # State.copy() churn.
    _bytes_cache: tuple | None = field(default=None, compare=False, repr=False)
    # Guarded memo of the fixed part of the `pb.Validator` row: fields
    # 1-3 (address, pub_key, voting_power), which ValidatorSet.to_bytes
    # writes before the priority. Kept as (address, pub_key,
    # voting_power, bytes); every read re-checks address (equality),
    # pub_key (identity) and voting_power, and copy() carries it, as
    # _bytes_cache.
    _row_cache: tuple | None = field(default=None, compare=False, repr=False)

    @classmethod
    def new(cls, pub_key: PubKey, voting_power: int) -> "Validator":
        return cls(address=pub_key.address(), pub_key=pub_key, voting_power=voting_power)

    def copy(self) -> "Validator":
        return Validator(
            self.address, self.pub_key, self.voting_power, self.proposer_priority,
            self._bytes_cache, self._row_cache,
        )

    def validate_basic(self) -> None:
        if self.pub_key is None:
            raise ValueError("validator does not have a public key")
        if self.voting_power < 0:
            raise ValueError("validator has negative voting power")
        if len(self.address) != 20:
            raise ValueError("validator address is the wrong size")

    def compare_proposer_priority(self, other: "Validator") -> "Validator":
        """Higher priority wins; ties break toward the lower address
        (ref: types/validator.go:101)."""
        if self.proposer_priority > other.proposer_priority:
            return self
        if self.proposer_priority < other.proposer_priority:
            return other
        if self.address < other.address:
            return self
        if self.address > other.address:
            return other
        raise ValueError("cannot compare identical validators")

    def bytes(self) -> bytes:
        """SimpleValidator proto encoding — the merkle leaf for
        ValidatorSet.Hash (ref: types/validator.go:154). Memoized with
        an input guard (see _bytes_cache)."""
        c = self._bytes_cache
        if c is not None and c[0] is self.pub_key and c[1] == self.voting_power:
            return c[2]
        enc = pb.SimpleValidator(
            pub_key=encoding.pubkey_to_proto(self.pub_key), voting_power=self.voting_power
        ).encode()
        self._bytes_cache = (self.pub_key, self.voting_power, enc)
        return enc

    def _fixed_row(self) -> bytes:
        """Fields 1-3 of `to_proto().encode()` (the priority left at 0),
        built by the codec whatever the key type and kept in _row_cache;
        ValidatorSet.to_bytes reads the memo itself and calls this where
        the guard fails."""
        enc = pb.Validator(
            address=self.address, pub_key=encoding.pubkey_to_proto(self.pub_key),
            voting_power=self.voting_power,
        ).encode()
        # tmcheck: ok[shared-mutation] idempotent lazy memo: racing fills store the same bytes, and every read re-checks the three fields
        self._row_cache = (self.address, self.pub_key, self.voting_power, enc)
        return enc

    def to_proto(self) -> pb.Validator:
        return pb.Validator(
            address=self.address,
            pub_key=encoding.pubkey_to_proto(self.pub_key),
            voting_power=self.voting_power,
            proposer_priority=self.proposer_priority,
        )

    @classmethod
    def from_proto(cls, p: pb.Validator) -> "Validator":
        return cls(
            address=p.address or b"",
            pub_key=encoding.pubkey_from_proto(p.pub_key),
            voting_power=p.voting_power or 0,
            proposer_priority=p.proposer_priority or 0,
        )


def _sorted_by_address(vals: list[Validator]) -> list[Validator]:
    return sorted(vals, key=lambda v: v.address)


def _sort_by_voting_power(vals: list[Validator]) -> None:
    # Descending power, ascending address (ref: ValidatorsByVotingPower,
    # types/validator_set.go:751).
    vals.sort(key=lambda v: (-v.voting_power, v.address))


@dataclass
class ValidatorSet:
    validators: list[Validator] = field(default_factory=list)
    proposer: Validator | None = None
    _total_voting_power: int = 0
    # Memoized merkle root of the SimpleValidator encodings. hash() is
    # called at least four times per block (state validation x2,
    # make_block x2, plus blocksync/light paths) and re-encoding +
    # re-merkling 1000 validators each time was the single biggest
    # structural-hash tax in the lifecycle. Cleared by EVERY mutating
    # method below (update / priority rotation / rescale), and never
    # carried across copy() — each copy rehashes once. Direct external
    # mutation of Validator objects bypasses the memo (nothing in-tree
    # does that; tests pin the invalidation paths).
    _hash_cache: bytes | None = field(default=None, compare=False, repr=False)

    @classmethod
    def new(cls, vals: list[Validator]) -> "ValidatorSet":
        """ref: NewValidatorSet (types/validator_set.go:47) — applies the
        update algorithm to an empty set, then shifts proposer rotation
        by one round."""
        vs = cls()
        vs._update_with_change_set(vals, allow_deletes=False)
        if vals:
            vs.increment_proposer_priority(1)
        return vs

    # -- accessors --------------------------------------------------------

    def is_nil_or_empty(self) -> bool:
        return not self.validators

    def size(self) -> int:
        return len(self.validators)

    def __len__(self) -> int:
        return len(self.validators)

    def copy(self) -> "ValidatorSet":
        return ValidatorSet(
            validators=[v.copy() for v in self.validators],
            proposer=self.proposer,
            _total_voting_power=self._total_voting_power,
        )

    def has_address(self, address: bytes) -> bool:
        return any(v.address == address for v in self.validators)

    def get_by_address(self, address: bytes) -> tuple[int, Validator | None]:
        for idx, v in enumerate(self.validators):
            if v.address == address:
                return idx, v.copy()
        return -1, None

    def get_by_index(self, index: int) -> tuple[bytes | None, Validator | None]:
        if index < 0 or index >= len(self.validators):
            return None, None
        v = self.validators[index]
        return v.address, v.copy()

    def total_voting_power(self) -> int:
        if self._total_voting_power == 0:
            self._update_total_voting_power()
        return self._total_voting_power

    def _update_total_voting_power(self) -> None:
        total = 0
        for v in self.validators:
            total = _clip64(total + v.voting_power)
            if total > MAX_TOTAL_VOTING_POWER:
                raise OverflowError(f"total voting power exceeds {MAX_TOTAL_VOTING_POWER}: {total}")
        # tmcheck: ok[shared-mutation] idempotent lazy memo: concurrent readers store the same total; mutation happens on the consensus thread
        self._total_voting_power = total

    def get_proposer(self) -> Validator | None:
        if not self.validators:
            return None
        if self.proposer is None:
            # tmcheck: ok[shared-mutation] idempotent lazy memo: priorities only move on the consensus thread, so every racing fill picks the same proposer
            self.proposer = self._find_proposer()
        return self.proposer.copy()

    def _find_proposer(self) -> Validator:
        result = None
        for v in self.validators:
            result = v if result is None else result.compare_proposer_priority(v)
        return result

    def _invalidate_hash(self) -> None:
        if self._hash_cache is not None:
            # tmcheck: ok[shared-mutation] idempotent lazy memo: racing fills compute identical roots; every mutation path (single consensus thread) clears here
            self._hash_cache = None
            hash_metrics().cache_events.add(1, "validator_set", "invalidate")

    def hash(self) -> bytes:
        """Merkle root of SimpleValidator encodings (ref: types/validator_set.go:344).
        Memoized; every mutating method clears the cache."""
        h = self._hash_cache
        if h is not None:
            hash_metrics().cache_events.add(1, "validator_set", "hit")
            return h
        h = hash_from_byte_slices([v.bytes() for v in self.validators], site="validator_set")
        self._hash_cache = h
        hash_metrics().cache_events.add(1, "validator_set", "miss")
        return h

    def validate_basic(self) -> None:
        if not self.validators:
            raise ValueError("validator set is nil or empty")
        if len(self.validators) > MAX_VOTES_COUNT:
            raise ValueError(f"validator set is too large: {len(self.validators)} > {MAX_VOTES_COUNT}")
        for v in self.validators:
            v.validate_basic()
        if self.proposer is None:
            raise ValueError("proposer failed validate basic, proposer is nil")
        self.proposer.validate_basic()

    # -- proposer rotation ------------------------------------------------

    def increment_proposer_priority(self, times: int) -> None:
        """ref: IncrementProposerPriority (types/validator_set.go:116)."""
        if self.is_nil_or_empty():
            raise ValueError("empty validator set")
        if times <= 0:
            raise ValueError("cannot call increment_proposer_priority with non-positive times")
        # priorities are not part of the leaf encoding, but the memo is
        # cleared on every mutation path by contract (cheap vs auditing
        # which mutations are hash-neutral)
        self._invalidate_hash()
        diff_max = PRIORITY_WINDOW_SIZE_FACTOR * self.total_voting_power()
        self.rescale_priorities(diff_max)
        self._shift_by_avg_proposer_priority()
        proposer = None
        for _ in range(times):
            proposer = self._increment_proposer_priority()
        self.proposer = proposer

    def copy_increment_proposer_priority(self, times: int) -> "ValidatorSet":
        c = self.copy()
        c.increment_proposer_priority(times)
        return c

    def _increment_proposer_priority(self) -> Validator:
        for v in self.validators:
            v.proposer_priority = _clip64(v.proposer_priority + v.voting_power)
        mostest = self._find_proposer()
        mostest.proposer_priority = _clip64(mostest.proposer_priority - self.total_voting_power())
        return mostest

    def rescale_priorities(self, diff_max: int) -> None:
        """Compress the priority spread below diff_max by integer division
        (ref: RescalePriorities, types/validator_set.go:142)."""
        if self.is_nil_or_empty():
            raise ValueError("empty validator set")
        if diff_max <= 0:
            return
        self._invalidate_hash()
        diff = self._max_min_priority_diff()
        ratio = (diff + diff_max - 1) // diff_max
        if diff > diff_max:
            for v in self.validators:
                # Go int division truncates toward zero; Python floors.
                q, r = divmod(v.proposer_priority, ratio)
                if r and v.proposer_priority < 0:
                    q += 1
                v.proposer_priority = q

    def _max_min_priority_diff(self) -> int:
        prios = [v.proposer_priority for v in self.validators]
        diff = max(prios) - min(prios)
        return -diff if diff < 0 else diff

    def _compute_avg_proposer_priority(self) -> int:
        n = len(self.validators)
        total = sum(v.proposer_priority for v in self.validators)
        # Go big.Int Div floors (Euclidean for positive divisor) — Python's
        # // matches for positive n.
        return total // n

    def _shift_by_avg_proposer_priority(self) -> None:
        avg = self._compute_avg_proposer_priority()
        for v in self.validators:
            v.proposer_priority = _clip64(v.proposer_priority - avg)

    # -- deterministic updates (ref: updateWithChangeSet, :584) -----------

    def update_with_change_set(self, changes: list[Validator]) -> None:
        self._update_with_change_set(changes, allow_deletes=True)

    def _update_with_change_set(self, changes: list[Validator], allow_deletes: bool) -> None:
        if not changes:
            return
        self._invalidate_hash()
        updates, deletes = _process_changes(changes)
        if not allow_deletes and deletes:
            raise ValueError(f"cannot process validators with voting power 0: {deletes}")
        num_new = sum(1 for u in updates if not self.has_address(u.address))
        if num_new == 0 and len(self.validators) == len(deletes):
            raise ValueError("applying the validator changes would result in empty set")
        removed_power = self._verify_removals(deletes)
        tvp_after_updates_before_removals = self._verify_updates(updates, removed_power)
        self._compute_new_priorities(updates, tvp_after_updates_before_removals)
        self._apply_updates(updates)
        self._apply_removals(deletes)
        self._update_total_voting_power()
        self.rescale_priorities(PRIORITY_WINDOW_SIZE_FACTOR * self.total_voting_power())
        self._shift_by_avg_proposer_priority()
        _sort_by_voting_power(self.validators)

    def _verify_removals(self, deletes: list[Validator]) -> int:
        removed = 0
        for d in deletes:
            _, val = self.get_by_address(d.address)
            if val is None:
                raise ValueError(f"failed to find validator {d.address.hex().upper()} to remove")
            removed += val.voting_power
        if len(deletes) > len(self.validators):
            raise ValueError("more deletes than validators")
        return removed

    def _verify_updates(self, updates: list[Validator], removed_power: int) -> int:
        """Checks the updated total power stays under the cap; returns the
        total power with updates applied but before removals
        (ref: verifyUpdates, types/validator_set.go:426)."""

        def delta(update: Validator) -> int:
            _, val = self.get_by_address(update.address)
            if val is not None:
                return update.voting_power - val.voting_power
            return update.voting_power

        tvp_after_removals = self.total_voting_power() - removed_power
        for upd in sorted(updates, key=delta):
            tvp_after_removals += delta(upd)
            if tvp_after_removals > MAX_TOTAL_VOTING_POWER:
                raise OverflowError("total voting power overflow")
        return tvp_after_removals + removed_power

    def _compute_new_priorities(self, updates: list[Validator], updated_total_voting_power: int) -> None:
        # New validators start at -1.125 * total power so un-bond/re-bond
        # can't reset a negative priority (ref: computeNewPriorities, :467).
        for u in updates:
            _, val = self.get_by_address(u.address)
            if val is None:
                u.proposer_priority = -(updated_total_voting_power + (updated_total_voting_power >> 3))
            else:
                u.proposer_priority = val.proposer_priority

    def _apply_updates(self, updates: list[Validator]) -> None:
        existing = _sorted_by_address(self.validators)
        merged: list[Validator] = []
        i = j = 0
        while i < len(existing) and j < len(updates):
            if existing[i].address < updates[j].address:
                merged.append(existing[i])
                i += 1
            else:
                merged.append(updates[j])
                if existing[i].address == updates[j].address:
                    i += 1
                j += 1
        merged.extend(existing[i:])
        merged.extend(updates[j:])
        self.validators = merged

    def _apply_removals(self, deletes: list[Validator]) -> None:
        if not deletes:
            return
        delete_addrs = {d.address for d in deletes}
        # tmcheck: ok[atomicity] validator-set updates run on the consensus thread against a private copy; readers see the old or new list reference atomically
        self.validators = [v for v in self.validators if v.address not in delete_addrs]

    # -- serialization ----------------------------------------------------

    def to_proto(self) -> pb.ValidatorSet:
        return pb.ValidatorSet(
            validators=[v.to_proto() for v in self.validators],
            proposer=self.proposer.to_proto() if self.proposer else None,
            total_voting_power=self.total_voting_power() if self.validators else 0,
        )

    def to_bytes(self) -> bytes:
        """`to_proto().encode()` in one pass, with no `pb` message made."""
        return self.encode_counted()[0]

    def encode_counted(self) -> tuple[bytes, int, int]:
        """`to_bytes()`, the rows it wrote (validators, then the proposer
        where there is one) and the rows whose fixed part was kept from
        an earlier encode (`Validator._row_cache`). A row is that part and
        field 4, the priority, left out when 0 as the codec leaves it."""
        parts: list[bytes] = []
        add = parts.append
        kept = 0
        rows = self.validators if self.proposer is None else [*self.validators, self.proposer]
        last = len(self.validators)
        for i, v in enumerate(rows):
            c = v._row_cache
            if c is not None and c[1] is v.pub_key and c[2] == v.voting_power and c[0] == v.address:
                row = c[3]
                kept += 1
            else:
                row = v._fixed_row()
            p = v.proposer_priority
            if p:
                row += _PRIORITY_TAG + _uvarint(p)
            add(_ROW_TAG if i < last else _PROPOSER_TAG)
            add(_uvarint(len(row)))
            add(row)
        if self.validators:
            total = self.total_voting_power()
            if total:
                add(_TOTAL_TAG)
                add(_uvarint(total))
        if kept:
            hash_metrics().cache_events.add(kept, "validator_row", "hit")
        if len(rows) > kept:
            hash_metrics().cache_events.add(len(rows) - kept, "validator_row", "miss")
        return b"".join(parts), len(rows), kept

    @classmethod
    def from_proto(cls, p: pb.ValidatorSet) -> "ValidatorSet":
        vs = cls(validators=[Validator.from_proto(v) for v in (p.validators or [])])
        if p.proposer is not None:
            vs.proposer = Validator.from_proto(p.proposer)
        return vs

    @classmethod
    def from_bytes(cls, buf: bytes, start: int = 0, end: int | None = None) -> "ValidatorSet":
        """`from_proto(pb.ValidatorSet.decode(buf[start:end]))` in one pass
        over the bytes: an equal set, or the same error. No `pb` message is
        made, and an ed25519 validator comes with its merkle leaf (`bytes()`)."""
        if buf.__class__ is not bytes:
            buf = bytes(buf)
        return _wire_decoder()(buf, start, len(buf) if end is None else end)


# -- from the wire in one pass (proto/message.py `decoder_to`) ---------------

# SimpleValidator{pub_key: PublicKey{ed25519: <32 bytes>}}: field 1, 34 bytes of field 1, 32 bytes
_ED25519_LEAF = b"\x0a\x22\x0a\x20"
_POWER_TAG = b"\x10"  # SimpleValidator.voting_power, left out when 0


def _pub_key_at(buf: bytes, pos: int, end: int):
    """A validator's `pub_key` sub-message, read where it lies. Exactly
    the ed25519 arm with 32 bytes (`0a 20` and the key): the key.
    Anything else (another arm, two arms, another length) is decoded as
    `pb.Validator.decode` decodes it, for `pubkey_from_proto` to judge."""
    if end - pos == 34 and buf[pos] == 0x0A and buf[pos + 1] == 0x20:
        return buf[pos + 2 : end]
    return pb.PublicKey.decode(buf[pos:end])


def _validator_row(*values):
    return values


def _validator_of(address, key, voting_power, proposer_priority) -> Validator:
    """What `Validator.from_proto` makes of the same four fields. The
    leaf is joined from the decoded key and power, never copied from the
    buffer: however a peer spelled them, the set hashes what they mean."""
    if key.__class__ is bytes:
        pub_key = Ed25519PubKey(key)
        leaf = _ED25519_LEAF + key
        if voting_power:
            leaf += _POWER_TAG + wire.encode_varint(voting_power)
        return Validator(address, pub_key, voting_power, proposer_priority, (pub_key, voting_power, leaf))
    return Validator(address, encoding.pubkey_from_proto(pb.PublicKey() if key is None else key), voting_power, proposer_priority)


def _validator_set_of(rows, proposer, _total_voting_power) -> ValidatorSet:
    # built after the last byte is read, validators first: a key that is
    # refused is refused after everything the decoder refuses, as in two passes
    vs = ValidatorSet(validators=[_validator_of(*row) for row in rows])
    if proposer is not None:
        vs.proposer = _validator_of(*proposer)
    return vs


@functools.cache
def _wire_decoder():
    row = pb.Validator.decoder_to(_validator_row, pub_key=_pub_key_at)
    return pb.ValidatorSet.decoder_to(_validator_set_of, validators=row, proposer=row)


def _process_changes(orig_changes: list[Validator]) -> tuple[list[Validator], list[Validator]]:
    """Split sorted changes into updates and removals, rejecting duplicates
    and invalid powers (ref: processChanges, types/validator_set.go:370)."""
    changes = _sorted_by_address([c.copy() for c in orig_changes])
    updates: list[Validator] = []
    removals: list[Validator] = []
    prev_addr = None
    for c in changes:
        if c.address == prev_addr:
            raise ValueError(f"duplicate entry {c} in changes")
        if c.voting_power < 0:
            raise ValueError(f"voting power can't be negative: {c.voting_power}")
        if c.voting_power > MAX_TOTAL_VOTING_POWER:
            raise ValueError(f"voting power can't be higher than {MAX_TOTAL_VOTING_POWER}: {c.voting_power}")
        if c.voting_power == 0:
            removals.append(c)
        else:
            updates.append(c)
        prev_addr = c.address
    return updates, removals
