"""The DPOR driver's record of admitted prescriptions, held in columns.

A prescription the racing scan derives is, by construction, its source
lane's first ``m - 1`` delivery rows plus one flipped row. So the log
keeps each harvested lane's delivery rows ONCE (a *chunk* holds one
round's lanes back to back) and, per admitted prescription, a row of
columns: the chunk, where the lane's rows start in it, the length ``m``,
the flipped row, and the 16-byte content digest. A Python tuple of row
tuples — what the explored set and the frontier used to hold for every
prescription — is built only where somebody asks for one
(``obs.stage_count("dpor.materialized")`` counts each).

``ExploredLog`` is the admission-ordered log, ``ExploredView`` the
read-only set the driver shows as ``explored``, and ``PrescList`` a list
of prescriptions held as indices into the log (frontier, generation,
round batch), whose items read as tuples.
"""

from __future__ import annotations

import collections.abc
from typing import Dict, Iterable, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from .. import obs

_SLAB = 4096  # prescriptions materialized at a time when streaming


class ExploredLog(collections.abc.Sequence):
    """Admission-ordered, append-only log of prescriptions in columnar
    form (see the module doc). Index 0 is the root — the empty
    prescription — of every driver. Reads as a sequence of tuples."""

    def __init__(self, rec_width: int, capacity: int = 256):
        self.width = int(rec_width)
        # chunks[0] is the empty chunk: the prefix of every prescription
        # of length <= 1.
        self.chunks: List[np.ndarray] = [np.zeros((0, self.width), np.int32)]
        self.n = 0
        self.chunk = np.zeros(capacity, np.int32)
        self.start = np.zeros(capacity, np.int32)
        self.length = np.zeros(capacity, np.int32)
        self.flip = np.zeros((capacity, self.width), np.int32)
        self.digest = np.zeros((capacity, 2), np.uint64)
        # Materialized tuples kept for re-use (what a sort compared, what
        # sleep mode keys its side tables by), and digest -> index for
        # the callers that arrive with a tuple.
        self._tuples: Dict[int, tuple] = {}
        self._index: Dict[bytes, int] = {}
        self._indexed = 0

    # -- writing -----------------------------------------------------------
    _COLUMNS = ("chunk", "start", "length", "flip", "digest")

    def _reserve(self, extra: int) -> None:
        need = self.n + extra
        cap = len(self.chunk)
        if need <= cap:
            return
        cap = max(need, 2 * cap)
        for name in self._COLUMNS:
            old = getattr(self, name)
            new = np.zeros((cap,) + old.shape[1:], old.dtype)
            new[: self.n] = old[: self.n]
            setattr(self, name, new)

    def add_chunk(self, rows: np.ndarray) -> int:
        """Keep ``rows`` ([n, width] int32, not written to afterwards);
        returns the chunk's id."""
        self.chunks.append(rows)
        return len(self.chunks) - 1

    def extend(self, chunk: int, starts, lengths, flips, digests) -> int:
        """Append prescriptions ``chunks[chunk][starts[k] : starts[k] +
        lengths[k] - 1] + flips[k]``; returns the first one's index."""
        k = len(starts)
        self._reserve(k)
        lo, hi = self.n, self.n + k
        self.chunk[lo:hi] = chunk
        self.start[lo:hi] = starts
        self.length[lo:hi] = lengths
        self.flip[lo:hi] = flips
        self.digest[lo:hi] = digests
        self.n = hi
        return lo

    def extend_tuples(
        self, prescriptions: Iterable[tuple], keep: bool = True
    ) -> int:
        """Append prescriptions that arrive as tuples of row tuples (a
        seed, a restored checkpoint, the legacy host path). Consecutive
        ones that share their prefix rows share one block, as the frames
        of a checkpoint do. ``keep`` keeps the tuples as given (they are
        not counted as materialized). Returns the first one's index."""
        from ..native import prefixed_digests

        first = self.n
        w = self.width
        rows: List[tuple] = []   # every block's rows, back to back
        at = 0                   # where the current block starts in rows
        starts, lengths, flips = [], [], []
        items = list(prescriptions)
        for p in items:
            m = len(p)
            pre = max(m - 1, 0)
            have = len(rows) - at
            k = 0
            while k < min(have, pre) and (
                rows[at + k] is p[k] or rows[at + k] == p[k]
            ):
                k += 1
            if k < pre:
                if k < have:
                    at = len(rows)   # diverges: a new block
                    k = 0
                rows.extend(p[k:pre])
            starts.append(at)
            lengths.append(m)
            flips.append(p[-1] if m else (0,) * w)
        if not items:
            return first
        chunk = self.add_chunk(
            np.asarray(rows, np.int32).reshape(len(rows), w)
        )
        flips = np.asarray(flips, np.int32).reshape(len(items), w)
        self.extend(
            chunk, starts, lengths, flips,
            prefixed_digests(self.chunks[chunk], starts, lengths, flips),
        )
        if keep:
            self._tuples.update(zip(range(first, self.n), items))
        return first

    def keep_tuples(self, first: int, tuples: Sequence[tuple]) -> None:
        """The tuples the caller built for entries ``first`` onwards
        (the per-candidate admission path), counted as materialized."""
        self._tuples.update(zip(range(first, first + len(tuples)), tuples))
        obs.stage_count("dpor.materialized", len(tuples))

    def copy(self) -> "ExploredLog":
        """An independent copy (chunks are shared: nothing writes to
        them) — the in-memory snapshot of the windowed oracle."""
        out = ExploredLog(self.width, capacity=max(1, self.n))
        out.chunks = list(self.chunks)
        out.n = self.n
        for name in self._COLUMNS:
            getattr(out, name)[: self.n] = getattr(self, name)[: self.n]
        out._tuples = dict(self._tuples)
        return out

    def restore(self, snapshot: "ExploredLog") -> None:
        """Become ``snapshot`` again, in place: lists that index this log
        stay bound to it."""
        fresh = snapshot.copy()
        self.__dict__.update(fresh.__dict__)

    # -- reading -----------------------------------------------------------
    def __len__(self) -> int:
        return self.n

    def key(self, i: int) -> bytes:
        return self.digest[i].tobytes()

    def index_of(self, prescription: tuple) -> int:
        """Index of an admitted prescription given as a tuple (KeyError
        when it was never admitted)."""
        from ..native import prescription_digest

        if not prescription:
            return 0
        if self._indexed < self.n:
            buf = self.digest[self._indexed: self.n].tobytes()
            for k in range(self.n - self._indexed):
                self._index.setdefault(
                    buf[16 * k: 16 * k + 16], self._indexed + k
                )
            self._indexed = self.n
        return self._index[prescription_digest(prescription)]

    def write_rows(self, i: int, out: np.ndarray) -> None:
        """Prescription ``i``'s rows into ``out`` ([>= rows, width]),
        cut to ``len(out)``."""
        m = min(int(self.length[i]), len(out))
        if m <= 0:
            return
        s = int(self.start[i])
        pre = min(m, int(self.length[i]) - 1)
        out[:pre] = self.chunks[self.chunk[i]][s: s + pre]
        if pre < m:
            out[pre] = self.flip[i]

    def _build(self, ids: Sequence[int]) -> List[tuple]:
        """Tuples for ``ids``; entries of one block share its row
        tuples, so comparing siblings is an identity hit per row."""
        ids = np.asarray(ids, np.int64)
        chunks = self.chunk[ids].tolist()
        starts = self.start[ids].tolist()
        lengths = self.length[ids].tolist()
        flips = self.flip[ids].tolist()
        need: Dict[Tuple[int, int], int] = {}
        for c, s, m in zip(chunks, starts, lengths):
            if m > 1 and need.get((c, s), 0) < m - 1:
                need[(c, s)] = m - 1
        blocks = {
            (c, s): list(map(tuple, self.chunks[c][s: s + k].tolist()))
            for (c, s), k in need.items()
        }
        out = []
        for c, s, m, f in zip(chunks, starts, lengths, flips):
            if m == 0:
                out.append(())
            elif m == 1:
                out.append((tuple(f),))
            else:
                out.append(tuple(blocks[(c, s)][: m - 1]) + (tuple(f),))
        obs.stage_count("dpor.materialized", len(out))
        return out

    def tuples_at(self, ids: Sequence[int], keep: bool = True) -> List[tuple]:
        """Prescriptions ``ids`` as tuples, from the kept ones where
        there; ``keep`` keeps what had to be built."""
        kept = self._tuples
        out = [kept.get(i) for i in ids]
        missing = [k for k, t in enumerate(out) if t is None]
        if missing:
            built = self._build([ids[k] for k in missing])
            for k, t in zip(missing, built):
                out[k] = t
                if keep:
                    kept[ids[k]] = t
        return out

    def tuple_at(self, i: int) -> tuple:
        t = self._tuples.get(i)
        return t if t is not None else self.tuples_at([i])[0]

    def stream(self, ids: Sequence[int]) -> Iterator[tuple]:
        """Prescriptions ``ids`` as tuples, a slab at a time, keeping
        none: for whole-log readers (a view's iteration, the codec)."""
        for lo in range(0, len(ids), _SLAB):
            yield from self.tuples_at(ids[lo: lo + _SLAB], keep=False)

    def __getitem__(self, i: int) -> tuple:
        if i < 0:
            i += self.n
        if not 0 <= i < self.n:
            raise IndexError(i)
        return self.tuple_at(i)

    def __iter__(self) -> Iterator[tuple]:
        return self.stream(range(self.n))

    def __eq__(self, other) -> bool:
        if isinstance(other, ExploredLog):
            n = self.n
            return n == other.n and all(
                np.array_equal(getattr(self, c)[:n], getattr(other, c)[:n])
                for c in ("length", "flip", "digest")
            )
        return NotImplemented

    __hash__ = None

    def __repr__(self) -> str:
        return f"ExploredLog(n={self.n}, chunks={len(self.chunks)})"


class ExploredView(collections.abc.Set):
    """The driver's ``explored``: a read-only set over the log and the
    digest set. ``len`` is the log's, ``p in explored`` digests ``p``
    and looks the digest up, iteration materializes in admission order;
    ``==``, ``-``, ``<=`` against builtin sets come from the mixin."""

    __slots__ = ("_dpor",)

    def __init__(self, dpor):
        self._dpor = dpor

    @classmethod
    def _from_iterable(cls, it):
        return set(it)

    def __len__(self) -> int:
        return len(self._dpor._explored_log)

    def __iter__(self) -> Iterator[tuple]:
        return iter(self._dpor._explored_log)

    def __contains__(self, prescription) -> bool:
        from ..native import prescription_digest

        try:
            key = prescription_digest(prescription)
        except (TypeError, ValueError):
            return False
        return key in self._dpor._explored_digests

    def __repr__(self) -> str:
        return f"ExploredView(n={len(self)})"


class PrescList(collections.abc.Sequence):
    """A list of admitted prescriptions held as indices into one log: the
    frontier, a generation, a round's batch. Items read as tuples of row
    tuples, so callers that compare, hash or iterate prescriptions see
    what the tuple lists showed; the driver works on ``idx`` and the
    log's columns. Index 0 (the root, the empty prescription) doubles as
    the padding lane.

    A list that ``DeviceDPOR._ordered_frontier`` ordered may carry
    *unsorted ranges*: runs of one depth bucket whose content order is
    not yet worked out. Every read that shows an order resolves the
    ranges it touches first, so the laziness is not observable; a round
    resolves only the buckets its batch reaches."""

    __slots__ = ("log", "idx", "_unsorted", "ordered")

    def __init__(self, log: ExploredLog, idx: Optional[Iterable[int]] = None):
        self.log = log
        self.idx: List[int] = [] if idx is None else list(idx)
        self._unsorted: List[Tuple[int, int]] = []
        # True while this is the output of the round-order rule and
        # nothing was added since: ordering it again changes nothing.
        self.ordered = False

    # -- the lazy order ----------------------------------------------------
    def _sort_range(self, r: int) -> None:
        """Work out the content order of unsorted range ``r``."""
        lo, hi = self._unsorted.pop(r)
        ids = self.idx[lo:hi]
        tuples = self.log.tuples_at(ids)
        order = sorted(range(len(ids)), key=tuples.__getitem__)
        self.idx[lo:hi] = [ids[j] for j in order]

    def _resolve(self, upto: Optional[int] = None) -> None:
        """Sort every unsorted range that starts before ``upto`` (all of
        them for None)."""
        while self._unsorted and (
            upto is None or self._unsorted[0][0] < upto
        ):
            self._sort_range(0)

    def split(self, n: int) -> Tuple["PrescList", "PrescList"]:
        """``(self[:n], self[n:])``; the tail keeps its unsorted
        ranges."""
        self._resolve(n)
        head = PrescList(self.log, self.idx[:n])
        rest = PrescList(self.log, self.idx[n:])
        rest._unsorted = [(lo - n, hi - n) for lo, hi in self._unsorted]
        head.ordered = rest.ordered = self.ordered
        return head, rest

    def by_depth_bucket(self, rows: int, head: int = 0) -> "PrescList":
        """This list in round order: the first ``head`` items stay,
        the rest go deepest bucket first (``rows`` rows to a bucket),
        each bucket left as an unsorted range."""
        ids = np.asarray(self.idx, np.int64)
        rest = ids[head:]
        bucket = self.log.length[rest] // rows
        order = np.argsort(-bucket, kind="stable")
        rest, bucket = rest[order], bucket[order]
        bounds = [0, *(np.flatnonzero(np.diff(bucket)) + 1).tolist(), len(rest)]
        out = PrescList(self.log, ids[:head].tolist() + rest.tolist())
        out._unsorted = [
            (lo + head, hi + head)
            for lo, hi in zip(bounds, bounds[1:]) if hi - lo > 1
        ]
        out.ordered = True
        return out

    def padded(self, n: int) -> "PrescList":
        """This list filled up to ``n`` with prescription-free lanes."""
        self._resolve()
        return PrescList(self.log, self.idx + [0] * (n - len(self.idx)))

    # -- writing -----------------------------------------------------------
    def append(self, i: int) -> None:
        self.idx.append(i)
        self.ordered = False

    def extend(self, ids: Iterable[int]) -> None:
        self.idx.extend(ids)
        self.ordered = False

    def insert(self, at: int, i: int) -> None:
        self._resolve()
        self.idx.insert(at, i)
        self.ordered = False

    def copy(self) -> "PrescList":
        out = PrescList(self.log, self.idx)
        out._unsorted = list(self._unsorted)
        out.ordered = self.ordered
        return out

    def __add__(self, other) -> "PrescList":
        if not isinstance(other, PrescList):
            other = PrescList(
                self.log, (self.log.index_of(p) for p in other)
            )
        out = PrescList(self.log, self.idx + other.idx)
        n = len(self.idx)
        out._unsorted = self._unsorted + [
            (lo + n, hi + n) for lo, hi in other._unsorted
        ]
        return out

    # -- reading -----------------------------------------------------------
    def __len__(self) -> int:
        return len(self.idx)

    def indices(self) -> List[int]:
        self._resolve()
        return self.idx

    def tuples(self) -> List[tuple]:
        """Every item as a tuple, kept in the log for the next reader."""
        return self.log.tuples_at(self.indices())

    def lengths(self) -> np.ndarray:
        """Rows of each prescription, in this list's current order."""
        return self.log.length[np.asarray(self.idx, np.int64)]

    def __getitem__(self, k):
        if isinstance(k, slice):
            self._resolve()
            return PrescList(self.log, self.idx[k])
        if k < 0:
            k += len(self.idx)
        for r, (lo, hi) in enumerate(self._unsorted):
            if lo <= k < hi:
                self._sort_range(r)
                break
        return self.log.tuple_at(self.idx[k])

    def __iter__(self) -> Iterator[tuple]:
        return self.log.stream(self.indices())

    def __eq__(self, other) -> bool:
        if isinstance(other, PrescList) and other.log is self.log:
            return self.indices() == other.indices()
        if isinstance(other, (PrescList, list, tuple)):
            return len(self) == len(other) and all(
                a == b for a, b in zip(self, other)
            )
        return NotImplemented

    __hash__ = None

    def __repr__(self) -> str:
        return f"PrescList(n={len(self.idx)})"
