"""Differential exploration: effect-diff class transfer (ROADMAP item 2).

The CI-scale product shape: when a tenant resubmits a *changed* system,
don't re-explore from scratch. A published class-store segment carries
an **effect-signature manifest** — per delivery tag, the digest of the
handler branch that tag dispatches to plus its read/write field sets
(``analysis/effects.py``), alongside digests of the dispatcher's shared
code, the invariant, and the init state. On warm start against a
changed app, ``compute_delta`` diffs stored vs current signatures into
a ``DeltaPlan``:

- **changed tags**: tags whose branch digest or effect sets moved;
- **contaminated cone**: the changed tags, closed transitively over
  field flow — when a change MOVES a field set, any tag reading one of
  the moved fields joins the cone and contributes its own writes, to a
  fixpoint. A pure code change with identical field sets keeps the
  cone at exactly the changed tags: the class-key delivery footprint is
  then precisely the invalidation criterion.
- **degradations** (sound by construction): ``unknown`` effects on
  either side, a moved shared/invariant/init digest, a tag-shape
  mismatch, or a changed tag with unknown field sets all contaminate
  everything — the plan goes ``full`` and the run is a scratch run.

``delta_warm_start`` then splits the stored classes against the cone at
**reversal-chain granularity**. Every class of a seeded exploration is
the seed prescription (the trunk) plus a chain of race reversals — one
per ancestry generation, each reordering exactly one dependent pair of
deliveries. The sleep set records that chain's tag footprint AT
ADMISSION, when the pair is exact knowledge, as ``dmask`` in the class
meta: the OR of ``tag_bit`` over BOTH rows of every reversed pair along
the class's derivation (see ``SleepSets.class_meta``). The transfer
test is ``dmask & cone_mask``: a class none of whose reversals involve
a cone tag TRANSFERS (``SleepSets.seed_covered`` — never re-executed);
a class whose chain touches the cone is RE-SEEDED onto the frontier via
its stored guide and re-explored for real. The trunk itself
(``TRUNK_BIT`` set, zero reversals) is ALWAYS re-seeded — its
re-execution under the edited app is the one run that revalidates the
shared schedule content every transferred class leans on. Classes with
no retained guide or no recorded chain (``dmask == -1``) fall back to
the full-key mask, which is strictly more conservative. Content lane
keys (``key_mode='content'``) make each re-execution bit-identical to
the scratch run's execution of the same prescription regardless of
round position, which is what lets ``--diff-audit`` demand equality,
not similarity: a full scratch exploration of the changed app must
yield the same class set, violation codes, and per-code canonical
witness digests as the differential run (``bench.py --config 17``).

Soundness caveat, stated where it matters: the chain mask covers the
REORDERINGS that distinguish a class from the trunk — the trunk content
every class replays (divergence-tolerant steering re-delivers the
source lane's remaining rows in order), including any cone-tag
deliveries in it, ran under the old binary and is vouched for by the
trunk revalidation plus the audit mode, not by the mask alone.
``unknown`` anywhere degrades to full scratch.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Set, Tuple

import numpy as np

from .effects import analyze_dsl_app, fn_digest
from .sleep import TRUNK_BIT, class_tag_mask, tag_bit

MANIFEST_VERSION = 1


def effect_manifest(app) -> Dict[str, Any]:
    """Per-tag effect-signature manifest of one DSLApp — the record a
    class-store segment carries so a LATER version can compute what its
    change contaminated. JSON-able and deterministic for a given app
    version."""
    from ..persist.checkpoint import handler_fingerprint

    eff = analyze_dsl_app(app)
    unknown = eff.failure is not None or not eff.per_tag
    tags: Dict[str, Any] = {}
    if not unknown:
        for t in sorted(eff.per_tag):
            e = eff.per_tag[t]
            tags[str(t)] = {
                "code": eff.tag_code.get(t, ""),
                "effects": e.to_json(),
            }
    return {
        "version": MANIFEST_VERSION,
        "fp": handler_fingerprint(app),
        "app": str(getattr(app, "name", "")),
        "actors": int(getattr(app, "num_actors", 0)),
        "n_tags": int(eff.n_tags),
        "unknown": bool(unknown),
        "failure": eff.failure,
        "shared": eff.shared_code,
        "invariant": fn_digest(getattr(app, "invariant", None)),
        "init": fn_digest(getattr(app, "init_state", None)),
        "tags": tags,
    }


@dataclass
class DeltaPlan:
    """What a code change contaminated, per ``compute_delta``."""

    full: bool
    reason: str = ""
    changed_tags: List[int] = field(default_factory=list)
    cone_tags: List[int] = field(default_factory=list)
    cone_mask: int = 0
    diff_fields: List[int] = field(default_factory=list)
    stored_fp: str = ""
    current_fp: str = ""

    def to_json(self) -> Dict[str, Any]:
        return {
            "full": self.full,
            "reason": self.reason,
            "changed_tags": list(self.changed_tags),
            "cone_tags": list(self.cone_tags),
            "cone_mask": int(self.cone_mask),
            "diff_fields": list(self.diff_fields),
            "stored_fp": self.stored_fp,
            "current_fp": self.current_fp,
        }


def _fields(sets: Dict[str, Any], kind: str) -> Optional[Set[int]]:
    v = sets.get(kind, "unknown")
    if v == "unknown":
        return None
    return {int(x) for x in v}


def compute_delta(
    stored: Optional[Dict[str, Any]], current: Optional[Dict[str, Any]]
) -> DeltaPlan:
    """Diff two effect-signature manifests into a ``DeltaPlan``. Every
    unanalyzable situation returns ``full=True`` — the differential
    path only ever SHRINKS work when it can prove the shrink."""

    def full(reason: str) -> DeltaPlan:
        return DeltaPlan(
            full=True, reason=reason,
            stored_fp=(stored or {}).get("fp", ""),
            current_fp=(current or {}).get("fp", ""),
        )

    if not stored or not current:
        return full("missing manifest")
    if stored.get("version") != current.get("version"):
        return full("manifest version mismatch")
    if stored.get("unknown") or current.get("unknown"):
        return full(
            "unknown effects: "
            + str(stored.get("failure") or current.get("failure") or "")
        )
    for k in ("app", "actors", "n_tags"):
        if stored.get(k) != current.get(k):
            return full(f"shape mismatch: {k}")
    for k in ("shared", "invariant", "init"):
        if stored.get(k) != current.get(k):
            return full(f"unattributable change: {k} digest moved")
    st, ct = stored.get("tags", {}), current.get("tags", {})
    if set(st) != set(ct):
        return full("tag set mismatch")

    changed: List[int] = []
    diff_fields: Set[int] = set()
    for key in sorted(st, key=int):
        a, b = st[key], ct[key]
        if a == b:
            continue
        t = int(key)
        changed.append(t)
        ea, eb = a.get("effects", {}), b.get("effects", {})
        for kind in ("reads", "writes", "or_writes"):
            fa, fb = _fields(ea, kind), _fields(eb, kind)
            if fa is None or fb is None:
                return full(f"changed tag {t} has unknown {kind}")
            diff_fields |= fa ^ fb
    if not changed:
        if stored.get("fp") == current.get("fp"):
            # Bit-identical code: empty cone, everything transfers.
            return DeltaPlan(
                full=False,
                stored_fp=stored.get("fp", ""),
                current_fp=current.get("fp", ""),
            )
        # Same signatures under a different fingerprint (e.g. the
        # change was outside the handler's visible surface): nothing
        # provably moved tag-locally, but the fingerprint layer saw
        # SOMETHING move that effects could not attribute.
        return full("fingerprint moved without attributable tag change")

    # Transitive field-flow closure (only field-set DIFFS propagate —
    # see module doc): a tag reading a contaminated field joins the
    # cone and contributes its writes.
    cone: Set[int] = set(changed)
    frontier = set(diff_fields)
    while True:
        grew = False
        for key in sorted(ct, key=int):
            t = int(key)
            if t in cone:
                continue
            e = ct[key].get("effects", {})
            reads = _fields(e, "reads")
            writes = _fields(e, "writes")
            orw = _fields(e, "or_writes") or set()
            if reads is None or writes is None:
                if frontier:
                    cone.add(t)
                    grew = True
                continue
            if reads & frontier or writes & frontier or orw & frontier:
                cone.add(t)
                new_fields = (writes | orw) - frontier
                if new_fields:
                    frontier |= new_fields
                grew = True
        if not grew:
            break

    cone_tags = sorted(cone)
    mask = 0
    for t in cone_tags:
        mask |= tag_bit(t)
    return DeltaPlan(
        full=False,
        changed_tags=sorted(changed),
        cone_tags=cone_tags,
        cone_mask=mask,
        diff_fields=sorted(diff_fields),
        stored_fp=stored.get("fp", ""),
        current_fp=current.get("fp", ""),
    )


def _ledger_mask(led, key: tuple) -> int:
    meta = led.meta.get(key)
    return int(meta[0]) if meta is not None else class_tag_mask(key)


def split_transfer(led, plan: DeltaPlan) -> Tuple[List[tuple], List[tuple]]:
    """Partition a stored ledger's classes against the plan's cone:
    (transferable, cone). Full plans transfer nothing. With a retained
    guide and a recorded reversal-chain mask the test is
    ``dmask & cone_mask`` (``TRUNK_BIT`` always cones — trunk
    revalidation); otherwise the full-key mask — a superset of any
    chain's footprint, so the fallback only ever moves classes INTO the
    cone."""
    if plan.full:
        return [], sorted(led.classes)
    transfer, cone = [], []
    for k in sorted(led.classes):
        meta = led.meta.get(k)
        guide = meta[2] if meta is not None else None
        dmask = int(meta[3]) if meta is not None and len(meta) > 3 else -1
        if guide is not None and dmask >= 0:
            contaminated = bool(dmask & (plan.cone_mask | TRUNK_BIT))
        else:
            contaminated = bool(_ledger_mask(led, k) & plan.cone_mask)
        (cone if contaminated else transfer).append(k)
    return transfer, cone


def delta_warm_start(dpor, store, app) -> Optional[Dict[str, Any]]:
    """Version-aware warm start for one DeviceDPOR against a
    ``ClassStore``. Returns a stats dict (also emitted as a
    ``dpor.delta`` journal record), or None when there is nothing to
    start from (no own-fp segments AND no sibling version) — the caller
    then runs scratch.

    - Own-fingerprint segments exist → **exact** mode: plain covered
      warm start (the PR 13 path) + full violation inheritance.
    - Else the best sibling version (most transferable classes) is
      diffed: transferable classes are seeded covered; cone classes
      that EXECUTED in the stored run are re-seeded onto the frontier
      with their stored guides (bit-identical re-execution under
      content lane keys); cone classes the stored run only admitted
      but never executed are noted un-executed, exactly matching what
      a scratch run would observe of them. Violation codes whose
      canonical witness class avoids the cone are inherited with their
      witness; cone-witnessed codes must be re-found live."""
    from .. import obs

    sleep = getattr(dpor, "sleep", None)
    if sleep is None:
        return None
    current = effect_manifest(app)
    own = store.load()
    stats: Dict[str, Any]
    if own.classes:
        sleep.seed_covered(own.classes, meta=own.meta)
        inherited_w = dict(own.witnesses)
        stats = {
            "mode": "exact",
            "full": False,
            "from_fp": store.workload_fp,
            "to_fp": current.get("fp", ""),
            "changed_tags": [],
            "cone_tags": [],
            "stored_classes": len(own.classes),
            "transferred": len(own.classes),
            "reseeded": 0,
            "pending": len(own.pending),
            "unseedable": 0,
            "inherited_codes": sorted(int(c) for c in own.violation_codes),
            "inherited_witnesses": inherited_w,
        }
    else:
        best = None
        for fp in store.sibling_fps():
            led = store.load_fp(fp)
            if not led.classes:
                continue
            plan = compute_delta(led.manifest, current)
            transfer, cone = split_transfer(led, plan)
            cand = (len(transfer), fp, led, plan, transfer, cone)
            if best is None or cand[0] > best[0] or (
                cand[0] == best[0] and fp < best[1]
            ):
                best = cand
        if best is None:
            return None
        _, from_fp, led, plan, transfer, cone = best
        stats = {
            "mode": "delta",
            "full": plan.full,
            "reason": plan.reason,
            "from_fp": from_fp,
            "to_fp": current.get("fp", ""),
            "changed_tags": plan.changed_tags,
            "cone_tags": plan.cone_tags,
            "diff_fields": plan.diff_fields,
            "stored_classes": len(led.classes),
            "transferred": 0,
            "reseeded": 0,
            "pending": 0,
            "unseedable": 0,
            "inherited_codes": [],
            "inherited_witnesses": {},
        }
        if not plan.full:
            cone_set = set(cone)
            sleep.seed_covered(transfer, meta=led.meta)
            stats["transferred"] = len(transfer)
            reseeded = unseedable = pending_noted = 0
            for k in cone:
                if k in sleep.classes:
                    continue
                meta = led.meta.get(k)
                if k in led.pending:
                    # Admitted but never executed in the stored run: a
                    # scratch run of the old version would not have
                    # executed it either — note it, don't run it.
                    sleep.note_class(k)
                    if meta is not None:
                        sleep.adopt_meta({k: meta})
                    pending_noted += 1
                    continue
                if meta is None or meta[2] is None:
                    unseedable += 1
                    continue
                plen, guide = meta[1], meta[2]
                dm = int(meta[3]) if len(meta) > 3 else -1
                rep = tuple(tuple(int(x) for x in r) for r in guide[:plen])
                sleep.note_class(k, guide=guide, plen=plen, dmask=dm)
                if rep in dpor.explored:
                    continue
                dpor.frontier.append(dpor.admit_tuples([rep]))
                dpor._guides[rep] = np.asarray(guide, np.int32)
                dpor._class_of[rep] = k
                reseeded += 1
            stats["reseeded"] = reseeded
            stats["unseedable"] = unseedable
            stats["pending"] = pending_noted
            inherited_w = {}
            for code, w in led.witnesses.items():
                wk = w.get("class")
                # Inherit exactly the witnesses whose class TRANSFERRED
                # (same membership test as the split above, so a
                # transferred-but-not-re-executed witness is never
                # silently dropped); cone-witnessed codes re-execute
                # and must be re-found live.
                if wk is None or wk in cone_set:
                    continue
                inherited_w[int(code)] = w
            stats["inherited_codes"] = sorted(inherited_w)
            stats["inherited_witnesses"] = inherited_w

    stats["skipped_launches"] = stats["transferred"] // max(
        1, int(getattr(dpor, "batch_size", 1) or 1)
    )
    obs.journal.emit(
        "dpor.delta",
        **{k: v for k, v in stats.items() if k != "inherited_witnesses"},
    )
    return stats


def build_run_ledger(dpor, app, inherited: Optional[Dict[str, Any]] = None):
    """Assemble the enriched ``ClassLedger`` one finished exploration
    publishes: classes + meta (masks always, guides when the sleep set
    retained them), pending (admitted-never-executed) classes, the
    current app's effect manifest, and per-code canonical witnesses —
    merged with witnesses inherited from the warm source so a
    republished store keeps its history."""
    from ..fleet.ledger import ClassLedger, _better_witness

    sleep = dpor.sleep
    led = ClassLedger(sleep.classes, dpor.violation_codes)
    for k in led.classes:
        led.meta[k] = sleep.class_meta.get(k) or (
            class_tag_mask(k), -1, None, -1
        )
    pending_prescs = {
        tuple(tuple(int(x) for x in r) for r in p) for p in dpor.frontier
    }
    led.pending = {
        k for p, k in dpor._class_of.items() if p in pending_prescs
    }
    led.manifest = effect_manifest(app)
    for code, w in dpor.violation_witnesses.items():
        led.witnesses[int(code)] = dict(w)
    if inherited:
        for code, w in (inherited.get("inherited_witnesses") or {}).items():
            code = int(code)
            cur = led.witnesses.get(code)
            led.witnesses[code] = (
                dict(w) if cur is None else _better_witness(cur, dict(w))
            )
        led.violation_codes.update(
            int(c) for c in inherited.get("inherited_codes", ())
        )
    return led


def effective_violations(
    dpor, stats: Optional[Dict[str, Any]] = None
) -> Tuple[List[int], Dict[int, str]]:
    """The run's violation verdict with warm inheritance folded in:
    (sorted codes, per-code canonical witness sha). Live findings and
    inherited records merge by min digest — order-free, so a
    differential run and a scratch run of behavior-identical code
    produce the same verdict."""
    from ..fleet.ledger import _better_witness

    codes: Set[int] = {int(c) for c in dpor.violation_codes}
    wits: Dict[int, Dict[str, Any]] = {
        int(c): dict(w) for c, w in dpor.violation_witnesses.items()
    }
    if stats:
        codes.update(int(c) for c in stats.get("inherited_codes", ()))
        for code, w in (stats.get("inherited_witnesses") or {}).items():
            code = int(code)
            codes.add(code)
            cur = wits.get(code)
            wits[code] = dict(w) if cur is None else _better_witness(
                cur, dict(w)
            )
    return sorted(codes), {
        c: str(w.get("sha", "")) for c, w in sorted(wits.items())
    }
