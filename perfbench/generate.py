"""The benchmark's one traffic generator: configuration + traffic mix + seed.

A traffic mix (perfbench/traffic/<mix>.json) describes one request as a
list of signature-set groups, and how requests are cut from the beacon
chain's slots:

- "request_per": "member" makes one request per committee member of each
  slot (unaggregated gossip), in a seeded order that interleaves the slot's
  committees; "committee" `per_committee` per committee (aggregators, a
  number or a preset name); "slot" one per slot (a block).
- Groups, each {"kind", "count", "keys", "participation"}:
  - "member": the request's own member's one-key set, over its committee's
    message;
  - "committee": a committee's aggregate, over its message, by round(
    participation x size) of its members; the slot's committees first, then
    the previous slot's;
  - "single": one random validator over a fresh message;
  - "sample": round(participation x keys) random validators over a fresh
    message (a sync aggregate).
  Counts and key counts are numbers or names of the configuration's
  `preset` constants.
- "tamper", one of two rules; both make a pair of sets swap signatures, so
  both sets are invalid and a batch check without random scalars would pass
  them (their sum is unchanged):
  - {"every", "at", "group"}: request i with i % every == at has two sets of
    that group swap signatures. The pair lies in the request's first half
    on even tampered requests and its second half on odd ones.
  - {"swap_requests": [p, ...]}: requests p and p + 1 of the stream (their
    first use only) swap their first sets' signatures: for one-set requests
    that a coalescer packs in stream order, p places the pair in a batch
    and in one half of it.

Committees follow the consensus specs' split of a slot's share of the
active validators (compute_committee): committees_per_slot from
TARGET_COMMITTEE_SIZE and MAX_COMMITTEES_PER_SLOT, sizes N/32/C rounded
down or up. Slot s's committees hold the validators
[(s * N/32) % M, ...) of the M materialized ones (the configuration's
`materialized_validators`); validator v's secret key is v + 1 (keys.py).
Messages come from the committed hash-to-G2 pool, from an offset drawn
from the seed. Generated requests are reused in order once the window has
used them all: the harness makes fresh signature objects for every use.
"""

from __future__ import annotations

import json
import pathlib
import random
from dataclasses import dataclass

from . import keys

POOL_FILE = pathlib.Path(__file__).resolve().parent / "data" / "h2g2_pool.json"


@dataclass
class SigSet:
    keys: list[int]  # validator indices; validator v signs with sk = v + 1
    msg: int  # index into the pool
    sig: tuple[int, int, int, int] | None  # affine G2 point
    valid: bool = True
    group: str = ""


@dataclass
class Stream:
    base: list[list[SigSet]]
    validators: int  # materialized: indices 0 .. validators-1
    tamper: dict | None = None
    seed: int = 0

    def request(self, i: int) -> list[SigSet]:
        """Request i of the stream: base request i % len(base), with the
        tamper rule applied by position i."""
        sets = self.base[i % len(self.base)]
        t = self.tamper
        if t and "swap_requests" in t:
            pairs = t["swap_requests"]
            other = i + 1 if i in pairs else i - 1 if i - 1 in pairs else None
            if other is None:
                return sets
            mine, theirs = sets[0], self.base[other % len(self.base)][0]
            return [SigSet(mine.keys, mine.msg, theirs.sig, False, mine.group), *sets[1:]]
        if not t or i % t["every"] != t["at"]:
            return sets
        a, b = self._pair(i, sets)
        out = list(sets)
        out[a] = SigSet(sets[a].keys, sets[a].msg, sets[b].sig, False, sets[a].group)
        out[b] = SigSet(sets[b].keys, sets[b].msg, sets[a].sig, False, sets[b].group)
        return out

    def _pair(self, i: int, sets: list[SigSet]) -> tuple[int, int]:
        half = (i // self.tamper["every"]) % 2
        lo, hi = (0, len(sets) // 2) if half == 0 else (len(sets) // 2, len(sets))
        group = [j for j in range(lo, hi) if sets[j].group == self.tamper["group"]]
        rng = random.Random(f"{self.seed}:tamper:{i}")
        a = rng.choice(group)
        b = rng.choice([j for j in group if sets[j].sig != sets[a].sig])
        return a, b

    def expected(self, i: int) -> list[bool]:
        return [s.valid for s in self.request(i)]


def load_pool(path=POOL_FILE) -> tuple[list[bytes], list[tuple[int, int, int, int]]]:
    data = json.loads(pathlib.Path(path).read_text())
    msgs = [bytes.fromhex(e[0]) for e in data["entries"]]
    pts = [tuple(int(c, 16) for c in e[1:]) for e in data["entries"]]
    return msgs, pts


def _value(cfg: dict, v):
    return cfg["preset"][v] if isinstance(v, str) else v


def committees_per_slot(cfg: dict) -> int:
    p = cfg["preset"]
    per_slot = cfg["active_validators"] // p["SLOTS_PER_EPOCH"]
    return max(1, min(p["MAX_COMMITTEES_PER_SLOT"], per_slot // p["TARGET_COMMITTEE_SIZE"]))


def slot_committees(cfg: dict, slot: int) -> list[range]:
    """Validator index ranges of the slot's committees."""
    per_slot = cfg["active_validators"] // cfg["preset"]["SLOTS_PER_EPOCH"]
    c = committees_per_slot(cfg)
    m = cfg["materialized_validators"]
    base = (slot * per_slot) % m
    if base + per_slot > m:
        raise ValueError("materialized_validators must be a multiple of a slot's validators")
    return [range(base + per_slot * i // c, base + per_slot * (i + 1) // c) for i in range(c)]


class _BulkSigner:
    """Collects sets whose signatures are computed in bulk at the end."""

    def __init__(self, pool_pts, msg_offset):
        self.pool_pts = pool_pts
        self.fresh = msg_offset  # next pool index for a fresh message
        self.muls = []  # (set, pool index, scalar)

    def next_msg(self) -> int:
        m = self.fresh % len(self.pool_pts)
        self.fresh += 1
        return m

    def aggregate(self, members: list[int], msg: int, group: str) -> SigSet:
        s = SigSet(sorted(members), msg, None, group=group)
        self.muls.append((s, msg, sum(v + 1 for v in members)))
        return s

    def finish(self) -> None:
        """Signatures of all collected sets: one-key sets over one message
        whose keys lie close together as one chain of additions, the rest
        by short multiplications."""
        by_msg: dict[int, list] = {}
        for item in self.muls:
            by_msg.setdefault(item[1], []).append(item)
        rest = []
        for msg, items in by_msg.items():
            lo = min(k for _, _, k in items)
            span = max(k for _, _, k in items) - lo + 1
            if len(items) > 8 and span <= 4 * len(items):
                chain = keys.g2_chain(self.pool_pts[msg], lo, span)
                for s, _, k in items:
                    s.sig = chain[k - lo]
            else:
                rest.extend(items)
        sigs = keys.g2_muls([(self.pool_pts[msg], k) for _, msg, k in rest])
        for (s, _, _), sig in zip(rest, sigs):
            s.sig = sig
        self.muls = []


def _participants(rng, members, participation: float) -> list[int]:
    members = list(members)
    return rng.sample(members, max(1, round(participation * len(members))))


def generate(cfg: dict, traffic: dict, seed: int, pool=None) -> Stream:
    """The stream of requests a cell's window consumes, from the seed."""
    msgs, pool_pts = pool if pool is not None else load_pool()
    rng = random.Random(f"perfbench:{seed}")
    n_slots = traffic["slots"]
    c = committees_per_slot(cfg)
    msg_offset = rng.randrange(len(pool_pts))
    committee_msg = lambda slot, i: (msg_offset + (slot * c + i)) % len(pool_pts)  # noqa: E731
    b = _BulkSigner(pool_pts, msg_offset + (n_slots + 1) * c)
    per = traffic["request_per"]
    groups = traffic["groups"]
    m = cfg["materialized_validators"]
    base: list[list[SigSet]] = []
    for slot in range(1, n_slots + 1):
        comms = slot_committees(cfg, slot)
        prev = slot_committees(cfg, slot - 1)
        if per == "member":
            units = [(ci, v) for ci, comm in enumerate(comms) for v in comm]
            rng.shuffle(units)
        elif per == "committee":
            n = _value(cfg, traffic.get("per_committee", 1))
            units = [(ci, None) for ci in range(len(comms)) for _ in range(n)]
            rng.shuffle(units)
        elif per == "slot":
            units = [(None, None)]
        else:
            raise ValueError(f"unknown request_per {per!r}")
        for ci, member in units:
            sets: list[SigSet] = []
            next_comm = 0
            for g in groups:
                kind = g["kind"]
                for _ in range(_value(cfg, g.get("count", 1))):
                    if kind == "member":
                        if member is None:
                            raise ValueError("a 'member' group needs request_per 'member'")
                        sets.append(b.aggregate([member], committee_msg(slot, ci), kind))
                    elif kind == "committee":
                        if ci is not None:
                            comm, msg = comms[ci], committee_msg(slot, ci)
                        else:
                            j = next_comm
                            next_comm += 1
                            k = j % (2 * c)
                            comm, msg = (comms[k], committee_msg(slot, k)) if k < c else (
                                prev[k - c], committee_msg(slot - 1, k - c))
                        part = _participants(rng, comm, g.get("participation", 1.0))
                        sets.append(b.aggregate(part, msg, kind))
                    elif kind == "single":
                        sets.append(b.aggregate([rng.randrange(m)], b.next_msg(), kind))
                    elif kind == "sample":
                        n = round(g.get("participation", 1.0) * _value(cfg, g["keys"]))
                        sets.append(b.aggregate(rng.sample(range(m), n), b.next_msg(), kind))
                    else:
                        raise ValueError(f"unknown set group kind {kind!r}")
            base.append(sets)
    b.finish()
    return Stream(base=base, validators=m, tamper=traffic.get("tamper"), seed=seed)
