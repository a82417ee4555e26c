"""Typed, versioned telemetry reports — the schema of record for
``QueryStats.extras``.

Every execution layer used to stuff an ad-hoc dict under its own
``stats.extras`` key (``plan``, ``enum``, ``ooc``, ``batch``, ``service``);
consumers had to reverse-engineer the keys from producer code and nothing
validated an exit path that forgot one.  These dataclasses are now the one
module of record: each producer *constructs* its report (``from_dict``
validates the exact key set and coerces numpy scalars to plain Python on
the way in), so a malformed report raises at the exit path that produced
it, not in a dashboard three layers later.

Backward compatibility: every report implements ``collections.abc.Mapping``
— ``report["chunks_read"]``, ``dict(report)``, ``set(report) ==
set(empty_enum_report())`` and ``report == {...}`` all behave exactly as
they did when the extras were plain dicts, so downstream code and tests
keep working unchanged.  ``SCHEMA_VERSION`` is a class attribute (not a
field): it versions the *shape* without perturbing the key set.
"""

from __future__ import annotations

import dataclasses
from collections.abc import Mapping
from dataclasses import dataclass, field

SCHEMA_VERSION = 1

_SCALARS = {
    int: int, float: float, bool: bool, str: str,
}


def _plain(v):
    """Recursively convert a report/np-scalar tree to plain Python."""
    if isinstance(v, Report):
        return v.to_dict()
    if isinstance(v, Mapping):
        return {k: _plain(x) for k, x in v.items()}
    if isinstance(v, (list, tuple)):
        t = type(v) if type(v) in (list, tuple) else list
        return t(_plain(x) for x in v)
    if hasattr(v, "item") and getattr(v, "shape", None) == ():
        return v.item()  # numpy scalar
    return v


class Report(Mapping):
    """Mapping-compatible dataclass base for all telemetry reports."""

    SCHEMA_VERSION = SCHEMA_VERSION

    # -- Mapping protocol ----------------------------------------------------

    def __getitem__(self, key):
        try:
            return getattr(self, key)
        except AttributeError:
            raise KeyError(key) from None

    def __iter__(self):
        return (f.name for f in dataclasses.fields(self))

    def __len__(self):
        return len(dataclasses.fields(self))

    def keys(self):
        return [f.name for f in dataclasses.fields(self)]

    def to_dict(self) -> dict:
        """Deep plain-dict copy (json-serializable modulo attr values)."""
        return {f.name: _plain(getattr(self, f.name))
                for f in dataclasses.fields(self)}

    # -- equality: a report equals any Mapping with the same plain content --

    def __eq__(self, other):
        if isinstance(other, Report):
            return self.to_dict() == other.to_dict()
        if isinstance(other, Mapping):
            return self.to_dict() == _plain(other)
        return NotImplemented

    def __ne__(self, other):
        eq = self.__eq__(other)
        return eq if eq is NotImplemented else not eq

    __hash__ = None  # mutable mapping semantics

    # -- construction + validation ------------------------------------------

    @classmethod
    def from_dict(cls, d: Mapping) -> "Report":
        """Build from a mapping with *exactly* this report's keys.

        This is the validation choke point every producer funnels through:
        missing or unknown keys raise immediately, and values are
        normalized (numpy → Python scalars) so reports are stable under
        json round-trips.
        """
        names = {f.name for f in dataclasses.fields(cls)}
        defaulted = {
            f.name for f in dataclasses.fields(cls)
            if f.default is not dataclasses.MISSING
            or f.default_factory is not dataclasses.MISSING
        }
        got = set(d.keys())
        missing = names - got - defaulted
        unknown = got - names
        if missing or unknown:
            raise ValueError(
                f"{cls.__name__}: schema v{cls.SCHEMA_VERSION} mismatch — "
                f"missing keys {sorted(missing)}, unknown keys "
                f"{sorted(unknown)}"
            )
        obj = cls(**{k: d[k] for k in got})
        obj.validate()
        return obj

    def validate(self) -> "Report":
        """Type-check every field against its annotation; returns self."""
        for f in dataclasses.fields(self):
            v = getattr(self, f.name)
            checker = getattr(self, f"_check_{f.name}", None)
            if checker is not None:
                checker(v)
                continue
            ann = f.type if isinstance(f.type, str) else getattr(
                f.type, "__name__", str(f.type))
            self._check_scalar(f.name, v, ann)
        return self

    def _check_scalar(self, name, v, ann):
        ok = {
            "int": lambda x: isinstance(x, (int,)) and not isinstance(x, bool),
            "float": lambda x: isinstance(x, (int, float))
            and not isinstance(x, bool),
            "bool": lambda x: isinstance(x, bool),
            "str": lambda x: isinstance(x, str),
            "str | None": lambda x: x is None or isinstance(x, str),
            "int | None": lambda x: x is None or isinstance(x, int),
        }.get(ann)
        if ok is not None and not ok(v):
            raise ValueError(
                f"{type(self).__name__}.{name}: expected {ann}, "
                f"got {type(v).__name__} ({v!r})"
            )

    def __post_init__(self):
        # normalize numpy scalars in place so getattr/json never leak them
        for f in dataclasses.fields(self):
            v = getattr(self, f.name)
            if hasattr(v, "item") and getattr(v, "shape", None) == ():
                object.__setattr__(self, f.name, v.item())


# ---------------------------------------------------------------------------
# Concrete reports, one per stats.extras key.
# ---------------------------------------------------------------------------


@dataclass(eq=False)
class PlanReport(Report):
    """``stats.extras["plan"]`` — planner decision for one query."""

    order: tuple
    source: str
    est_cost: float
    fingerprint: object
    plan_seconds: float

    def _check_order(self, v):
        if not isinstance(v, tuple):
            raise ValueError(f"PlanReport.order: expected tuple, got "
                             f"{type(v).__name__}")

    def _check_fingerprint(self, v):
        pass  # opaque planner token (hash tuple or None)

    def __post_init__(self):
        object.__setattr__(self, "order", tuple(self.order))
        super().__post_init__()

    @classmethod
    def skipped(cls) -> "PlanReport":
        """The filter-killed contract: planner present, nothing to order."""
        return cls(order=(), source="skipped", est_cost=0.0,
                   fingerprint=None, plan_seconds=0.0)


@dataclass(eq=False)
class EnumLevel(Report):
    """One per-level record of ``EnumReport.levels``."""

    level: int
    emit_rows: list
    rebalanced: bool
    rebalance_seconds: float

    def _check_emit_rows(self, v):
        if not isinstance(v, list) or not all(
                isinstance(x, int) for x in v):
            raise ValueError("EnumLevel.emit_rows: expected list[int], "
                             f"got {v!r}")

    def __post_init__(self):
        object.__setattr__(
            self, "emit_rows", [int(x) for x in self.emit_rows]
        )
        super().__post_init__()


@dataclass(eq=False)
class EnumReport(Report):
    """``stats.extras["enum"]`` — two-phase device-join telemetry.

    Field semantics are documented at the producer
    (``core.search.empty_enum_report``) and in docs/OBSERVABILITY.md; the
    plain-dict schema the searchers fill and this dataclass must stay in
    lockstep (``empty_enum_report()`` is generated from ``empty()``, so
    they cannot drift).
    """

    device_rounds: int
    host_levels: int
    count_seconds: float
    scan_seconds: float
    emit_seconds: float
    max_table_rows: int
    max_emit_rows: int
    scan_path: "str | None"
    enum_shards: int
    emit_rows_max: int
    emit_rows_min: int
    rebalance_rounds: int
    rebalance_rows_moved: int
    rebalance_seconds: float
    host_syncs: int
    levels: list = field(default_factory=list)

    def _check_levels(self, v):
        if not isinstance(v, list):
            raise ValueError("EnumReport.levels: expected list")
        for lvl in v:
            if not isinstance(lvl, EnumLevel):
                raise ValueError(
                    "EnumReport.levels: expected EnumLevel entries, got "
                    f"{type(lvl).__name__}"
                )
            lvl.validate()

    def __post_init__(self):
        object.__setattr__(self, "levels", [
            lvl if isinstance(lvl, EnumLevel) else EnumLevel.from_dict(lvl)
            for lvl in self.levels
        ])
        super().__post_init__()

    @classmethod
    def empty(cls) -> "EnumReport":
        return cls(
            device_rounds=0, host_levels=0,
            count_seconds=0.0, scan_seconds=0.0, emit_seconds=0.0,
            max_table_rows=0, max_emit_rows=0,
            scan_path=None, enum_shards=0,
            emit_rows_max=0, emit_rows_min=0,
            rebalance_rounds=0, rebalance_rows_moved=0,
            rebalance_seconds=0.0, host_syncs=0, levels=[],
        )

    def _check_scan_path(self, v):
        if v is not None and v not in ("device", "host"):
            raise ValueError(
                f"EnumReport.scan_path: expected 'device'/'host'/None, "
                f"got {v!r}"
            )


@dataclass(eq=False)
class OocReport(Report):
    """``stats.extras["ooc"]`` — chunk-IO telemetry for one epoch/fetch.

    ``fetches`` counts ``fetch_restricted`` calls aggregated into this
    report (1 for a single engine fetch; the service accumulates per
    epoch).  ``n_chunks`` / ``peak_resident_bytes`` /
    ``resident_budget_bytes`` are point-in-time gauges; everything else
    sums across fetches.  ``partial=True`` marks a report produced on the
    ``ChunkIOError`` failure path — counters cover only the work done
    before the fault.
    """

    chunks_read: int
    cache_hits: int
    cache_misses: int
    bytes_read: int
    n_chunks: int
    edges_fetched: int
    peak_resident_bytes: int
    resident_budget_bytes: int
    fetch_seconds: float
    fetches: int = 1
    partial: bool = False

    GAUGES = ("n_chunks", "peak_resident_bytes", "resident_budget_bytes",
              "partial")

    def merge(self, other: Mapping) -> "OocReport":
        """Accumulate another fetch into this epoch-level report."""
        d = self.to_dict()
        for k, v in other.items():
            if k in self.GAUGES:
                d[k] = bool(d[k] or v) if k == "partial" else v
            else:
                d[k] = d.get(k, 0) + v
        return OocReport.from_dict(d)


@dataclass(eq=False)
class BatchReport(Report):
    """``stats.extras["batch"]`` — shape-bucket placement of one query."""

    bucket: tuple
    batch_size: int

    def _check_bucket(self, v):
        if not (isinstance(v, tuple) and len(v) == 3):
            raise ValueError(
                f"BatchReport.bucket: expected (d_max, l_pad, u_pad), "
                f"got {v!r}"
            )

    def __post_init__(self):
        object.__setattr__(
            self, "bucket", tuple(int(x) for x in self.bucket)
        )
        super().__post_init__()


@dataclass(eq=False)
class ServiceReport(Report):
    """``stats.extras["service"]`` — scheduling facts for one request.

    The admission-control fields default to the single-tenant/no-deadline
    values so pre-admission-control report dicts still round-trip through
    ``from_dict``.  ``deadline_missed`` records a request that *completed*
    after its deadline passed (admission expires still-queued ones
    instead; see serve/graph_service.py).
    """

    slot: int
    epoch: int
    queue_seconds: float
    rounds: int = 0
    trace_id: "int | None" = None
    tenant: str = "default"
    priority: int = 0
    deadline_missed: bool = False


REPORT_TYPES: dict[str, type] = {
    "plan": PlanReport,
    "enum": EnumReport,
    "ooc": OocReport,
    "batch": BatchReport,
    "service": ServiceReport,
}


def validate_extras(extras: Mapping) -> None:
    """Assert every known ``stats.extras`` key carries its typed report.

    Test harnesses sweep this across exit paths; unknown keys (scalars
    like ``shards`` / ``store_prefilter_alive``) pass through untouched.
    """
    for key, cls in REPORT_TYPES.items():
        if key in extras:
            rep = extras[key]
            if not isinstance(rep, cls):
                raise ValueError(
                    f"extras[{key!r}]: expected {cls.__name__}, got "
                    f"{type(rep).__name__}"
                )
            rep.validate()
