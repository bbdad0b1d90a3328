"""StaticIndependence: the conservative may-commute relation DPOR consumes.

Two racing same-receiver deliveries can be skipped by the backtrack
derivation when the flip is provably a no-op:

  - **fungible** — the two records are content-identical in every column
    the prescribed-dispatch matcher consults (kind, receiver, payload,
    and sender for non-timers). Delivering either record prescribes the
    *same* lowest-seq pool entry, so the "flipped" prescription denotes
    the schedule the lane already executed — the identity flip. This is
    the static half of DEMi's wildcard/fungible-clock insight: identical
    messages are exchangeable. Sound for ANY handler.
  - **commute** — the static field-effect analysis (analysis/effects.py)
    proves the two message tags' handler effects commute on the receiver
    (disjoint read/write sets; |=-accumulations commute among
    themselves). Exported to the device tier as a fixed-shape boolean
    matrix so the batch-native scan (``demi_racing_prescriptions_static``)
    and the NumPy fallback consult it per round with no Python per-pair
    work.

Unsoundness is impossible by construction: an unanalyzable handler
yields UNKNOWN effects, UNKNOWN conflicts with everything, and the
fungible rule is handler-independent. The ``analysis.static_pruned``
counters (labels: kind=fungible|commute, tier=device|host) quantify the
schedule-space reduction next to the existing ``redundant`` /
``distance-pruned`` gauges; ``audit=True`` additionally materializes
every pruned prescription so the bench can assert that pruning removed
exactly the no-ops and nothing else.

Off by default everywhere: DeviceDPOR / DPORScheduler take
``static_independence=`` explicitly, or build one from the app under
``DEMI_STATIC_PRUNE=1`` / ``--static-prune``.
"""

from __future__ import annotations

import os
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from .effects import (
    ActorEffects,
    AppEffects,
    analyze_actor_class,
    analyze_dsl_app,
    effects_commute,
)

REC_TIMER = 2  # device/core.py REC_TIMER (kept in sync by test_lint)


def static_prune_enabled(explicit: Optional[bool] = None) -> bool:
    """Resolve the static-pruning switch: explicit arg wins, else the
    ``DEMI_STATIC_PRUNE`` env flag. Off by default (every schedule-space
    feature in this repo ships opt-in with pinned parity)."""
    if explicit is not None:
        return bool(explicit)
    return os.environ.get("DEMI_STATIC_PRUNE", "").strip().lower() in (
        "1", "true", "yes", "on"
    )


class StaticIndependence:
    """May-commute relation over one app's message tags (device tier)
    and/or host actor classes, plus the fungible-flip rule.

    The object also carries the pruning ledger: ``pruned_total`` counts
    by kind, and (``audit=True``) ``pruned_prescriptions`` keeps every
    pruned prescription materialized for the bench/test no-op check."""

    def __init__(
        self,
        app_effects: Optional[AppEffects] = None,
        fungible: bool = True,
        audit: bool = False,
        actor_effects: Optional[Dict[str, ActorEffects]] = None,
    ):
        self.app_effects = app_effects
        self.fungible = bool(fungible)
        self.audit = bool(audit)
        self.actor_effects = actor_effects or {}
        self.pruned_total: Dict[str, int] = {"fungible": 0, "commute": 0}
        self.pruned_prescriptions: List[Tuple[Tuple[int, ...], ...]] = []
        self._matrix: Optional[np.ndarray] = None

    # -- constructors ------------------------------------------------------
    @classmethod
    def for_app(cls, app, fungible: bool = True, audit: bool = False
                ) -> "StaticIndependence":
        """Analyze a DSLApp's handler (analysis failure => a relation
        whose commute half declares nothing independent)."""
        return cls(
            app_effects=analyze_dsl_app(app), fungible=fungible, audit=audit
        )

    @classmethod
    def for_actor_classes(
        cls, classes: Dict[str, type], fungible: bool = True
    ) -> "StaticIndependence":
        """Host-tier relation over named Actor classes (keys are actor
        names or name prefixes; values are Actor subclasses)."""
        return cls(
            actor_effects={
                name: analyze_actor_class(c) for name, c in classes.items()
            },
            fungible=fungible,
        )

    # -- the relation ------------------------------------------------------
    def may_commute(self, tag1: int, tag2: int) -> bool:
        """Do deliveries of tags ``tag1`` and ``tag2`` to the same actor
        provably commute (DSL-app tier)? Unknown tags never commute."""
        eff = self.app_effects
        if eff is None:
            return False
        t1, t2 = int(tag1), int(tag2)
        if not (0 <= t1 <= eff.n_tags and 0 <= t2 <= eff.n_tags):
            return False
        return effects_commute(eff.effect_for(t1), eff.effect_for(t2))

    def device_matrix(self) -> Optional[np.ndarray]:
        """Fixed-shape uint8 [M, M] may-commute matrix over message tags
        (M = n_tags + 2; the last row/column is the catch-all for
        out-of-range tags and is all-False). None when no app analysis
        is attached — the scans then apply only the fungible rule."""
        if self.app_effects is None:
            return None
        if self._matrix is None:
            n = self.app_effects.n_tags
            m = n + 2
            mat = np.zeros((m, m), np.uint8)
            for a in range(0, n + 1):
                for b in range(a, n + 1):
                    if self.may_commute(a, b):
                        mat[a, b] = mat[b, a] = 1
            self._matrix = np.ascontiguousarray(mat)
        return self._matrix

    # -- per-pair predicate (host tier) -----------------------------------
    def host_commutes_kind(self, ev_i, ev_j) -> Optional[str]:
        """'fungible' / 'commute' / None for a host-tier DporEvent pair
        (same receiver by construction of the racing scan)."""
        if self.fungible and (
            ev_i.fingerprint == ev_j.fingerprint
            and ev_i.is_timer == ev_j.is_timer
            and ev_i.rcv == ev_j.rcv
            and (ev_i.is_timer or ev_i.snd == ev_j.snd)
        ):
            return "fungible"
        if self.app_effects is not None:
            t1 = _fp_tag(ev_i.fingerprint)
            t2 = _fp_tag(ev_j.fingerprint)
            if t1 is not None and t2 is not None and self.may_commute(t1, t2):
                return "commute"
        if self.actor_effects:
            eff = self._actor_effects_for(ev_i.rcv)
            if eff is not None:
                e1 = eff.effect_for(_fp_type_key(ev_i.fingerprint))
                e2 = eff.effect_for(_fp_type_key(ev_j.fingerprint))
                if effects_commute(e1, e2):
                    return "commute"
        return None

    def _actor_effects_for(self, rcv: str) -> Optional[ActorEffects]:
        if rcv in self.actor_effects:
            return self.actor_effects[rcv]
        for prefix, eff in self.actor_effects.items():
            if rcv.startswith(prefix):
                return eff
        return None

    # -- pruning ledger ----------------------------------------------------
    def note_pruned(
        self, fungible: int = 0, commute: int = 0, tier: str = "device"
    ) -> None:
        """Fold one scan's prune counts into the ledger + obs counters."""
        from .. import obs

        if fungible:
            self.pruned_total["fungible"] += int(fungible)
            obs.counter("analysis.static_pruned").inc(
                int(fungible), kind="fungible", tier=tier
            )
        if commute:
            self.pruned_total["commute"] += int(commute)
            obs.counter("analysis.static_pruned").inc(
                int(commute), kind="commute", tier=tier
            )

    def note_pruned_prescription(
        self, prescription: Tuple[Tuple[int, ...], ...]
    ) -> None:
        if self.audit:
            self.pruned_prescriptions.append(prescription)

    @property
    def pruned(self) -> int:
        return sum(self.pruned_total.values())

    def summary(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {
            "fungible": self.fungible,
            "pruned": dict(self.pruned_total),
        }
        if self.app_effects is not None:
            pairs = []
            n = self.app_effects.n_tags
            for a in range(1, n + 1):
                for b in range(a, n + 1):
                    if self.may_commute(a, b):
                        pairs.append([a, b])
            out["commuting_tag_pairs"] = pairs
            out["analysis_failure"] = self.app_effects.failure
        return out


def _rows_fungible(row_i, row_j, w: int) -> bool:
    """Content-identity over the matchable columns of two device records:
    kind, dst, payload — and src only for non-timers (prescribed dispatch
    never matches a timer's src). parent/prev (the last two columns) are
    bookkeeping, not content."""
    if int(row_i[0]) != int(row_j[0]) or int(row_i[2]) != int(row_j[2]):
        return False
    for c in range(3, w - 2):
        if int(row_i[c]) != int(row_j[c]):
            return False
    return int(row_i[0]) == REC_TIMER or int(row_i[1]) == int(row_j[1])


def _fp_tag(fp) -> Optional[int]:
    """Message tag of a host-tier fingerprint: DSL messages fingerprint
    to their int tuples, whose first element is the tag."""
    if (
        isinstance(fp, tuple)
        and fp
        and isinstance(fp[0], int)
        and not isinstance(fp[0], bool)
    ):
        return fp[0]
    return None


def _fp_type_key(fp) -> Any:
    """Dispatch key of a host-tier fingerprint for Actor-class effects:
    the leading tag of tuple messages, or the dataclass/type name the
    BaseFingerprinter embeds."""
    if isinstance(fp, tuple) and fp:
        return fp[0]
    return fp
