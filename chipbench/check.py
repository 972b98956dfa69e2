"""The part of ``correct`` that every kind of the service shares: what
the driver fed the service, and the blocks and re-admissions the
service's stated rules make of it.

The reference's input is the driver's own record of what it submitted,
tick by tick (``fed``, ``asked``), never what the program recorded of
it; from it ``expected_feed`` builds the blocks and re-admissions the
service's stated rules call for, and ``feed_differing`` counts the
window's blocks and re-admissions in which the program's differ
(exact): an update dropped, credited to another tenant or put off to a
later tick shows there. The kind module (``chipbench/kinds/``) replays
its reference over the expected blocks and compares the answers.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

import numpy as np

Update = Tuple[int, np.ndarray, np.ndarray]    # (tenant, items, weights)


@dataclasses.dataclass
class Fed:
    """What the driver handed the service, and what the program fed its
    ingest, as the check needs them; a kind's record adds its answers."""

    item_bits: int
    block: int
    tenants: int
    spill_after: Optional[int]
    kept: List[int]                          # subscribed: never spill
    # per tick since the service was built: the driver's updates in
    # submission order, and the tenants it queried
    fed: List[List[Update]]
    asked: List[List[int]]
    first_tick: int                          # the window's first tick
    # what the program fed its ingest, and re-admitted, in the window
    program_blocks: List[Tuple[np.ndarray, np.ndarray]]
    program_admits: List[Tuple[int, int]]    # (block index, tenant)
    unacknowledged: int


@dataclasses.dataclass
class Feed:
    """The blocks and re-admissions the driver's submissions call for."""

    blocks: List[Tuple[np.ndarray, np.ndarray]]
    admits: List[Tuple[int, int]]            # (block index, tenant)
    # per tick: (blocks before, blocks after, admits before, admits after)
    ticks: List[Tuple[int, int, int, int]]


def expected_feed(rec: Fed) -> Feed:
    """Each tick's blocks and re-admissions under the service's stated
    rules, from the driver's own record of what it submitted:

    * a tick first re-admits, in ascending tenant order, every spilled
      tenant it has an update or a query for;
    * it then feeds every update submitted since the last tick: tenants
      ascending, each tenant's in submission order, keyed
      ``tenant << item_bits | item``, cut into blocks of ``block`` keys,
      the last padded with weight-0 key-0 entries;
    * last, a tenant whose last update or re-admission is ``spill_after``
      ticks old or more, and that no subscription keeps, spills.
    """
    T, B, bits = rec.tenants, rec.block, rec.item_bits
    last = np.zeros(T, np.int64)
    seen = np.zeros(T, bool)
    spilled = np.zeros(T, bool)
    kept = np.zeros(T, bool)
    kept[list(rec.kept)] = True
    out = Feed([], [], [])
    for i, (ups, asked) in enumerate(zip(rec.fed, rec.asked)):
        b0, a0 = len(out.blocks), len(out.admits)
        touched = np.unique(np.asarray(
            [t for t, _, _ in ups] + list(asked), np.int64))
        for t in touched[spilled[touched]].tolist():
            out.admits.append((len(out.blocks), t))
            spilled[t], last[t] = False, i
        if ups:
            ten = np.asarray([t for t, _, _ in ups], np.int64)
            order = np.argsort(ten, kind="stable")
            lens = np.asarray([len(ups[j][1]) for j in order])
            keys = ((np.repeat(ten[order], lens) << bits)
                    | np.concatenate([ups[j][1] for j in order]
                                     ).astype(np.int64)).astype(np.int32)
            w = np.concatenate([ups[j][2] for j in order]).astype(np.int32)
            for s in range(0, len(keys), B):
                ci, cw = keys[s:s + B], w[s:s + B]
                out.blocks.append((np.pad(ci, (0, B - len(ci))),
                                   np.pad(cw, (0, B - len(cw)))))
            last[ten], seen[ten] = i, True
        if rec.spill_after is not None:
            spilled |= seen & ~kept & (i - last >= rec.spill_after)
        out.ticks.append((b0, len(out.blocks), a0, len(out.admits)))
    return out


def feed_differing(rec: Fed, feed: Feed) -> int:
    """Blocks and re-admissions of the window in which the program's
    differ from those the driver's submissions call for (each missing or
    extra one counts)."""
    b0, _, a0, _ = feed.ticks[rec.first_tick] if rec.first_tick < len(
        feed.ticks) else (len(feed.blocks), 0, len(feed.admits), 0)
    want_b, got_b = feed.blocks[b0:], rec.program_blocks
    n = abs(len(want_b) - len(got_b))
    for (wi, ww), (gi, gw) in zip(want_b, got_b):
        n += int(not (np.array_equal(wi, np.asarray(gi, np.int32))
                      and np.array_equal(ww, np.asarray(gw, np.int32))))
    want_a = [(b - b0, t) for b, t in feed.admits[a0:]]
    got_a = [(int(b), int(t)) for b, t in rec.program_admits]
    n += abs(len(want_a) - len(got_a))
    n += sum(int(w != g) for w, g in zip(want_a, got_a))
    return n
