"""PimDatabase: the PIM-resident database copy + unified query execution.

``PimDatabase.execute(spec_or_specs, *, engine=Engine.FUSED)`` is the one
entry point:

  * a single ``QuerySpec`` returns one :class:`QueryResult`; a sequence
    returns one result per spec in batch order (``[]`` for an empty
    batch, a one-element list for a singleton — no link/dispatch edge
    case);
  * multi-spec FUSED batches are cross-query fused: compiled
    independently (canonicalized, namespaced), grouped by relation,
    linked into ONE SSA program per relation
    (``core.program.link_programs``) and dispatched once per relation;
  * ``engine`` picks the substrate: ``Engine.FUSED`` (one compiled jax
    dispatch per relation program — the paper's single-readout model),
    ``Engine.EAGER`` (instruction-at-a-time PIM engine, the oracle),
    ``Engine.ORACLE`` (numpy column-store scan, the paper's §5.5
    comparison point).

Specs with a host stage run END TO END (PIM filter + in-dispatch
materialization + host join/agg/order into full TPC-H rows); specs
without one keep the paper's filter/aggregate scope.  The batch path is
split-phase for the async serving layer (``repro.serve``):
``dispatch_batch`` compiles, links and runs the array stage only, and
``finish_query`` completes each query's host stage — so a worker pool
can drain host stages while the next admission window dispatches.
``run_pim``/``run_query``/``run_queries`` remain as deprecated shims.
The module also produces the paper-faithful cost report (cycles, read
traffic, modeled latency/energy at any scale factor, incl. SF=1000).
"""
from __future__ import annotations

import dataclasses
import enum
import time
import warnings
from typing import Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from repro import analysis, obs
from repro.core import cost_model as cm
from repro.core import engine as eng
from repro.core import isa
from repro.core import program as prog
from . import exec as E
from . import queries as Q
from . import schema as S
from .compiler import And, Compiler, predicate_attrs


class _CostStat:
    """A :class:`RelationRun` field that only the cost report reads.  A
    value given at construction is kept; ``None`` (the default) is
    computed on first read, with its sibling, by
    ``RelationRun._fill_cost_stats`` and cached on the instance."""

    def __set_name__(self, owner, name):
        self.key = "_" + name

    def __get__(self, run, owner=None):
        if run is None:
            return None                  # the dataclass field's default
        if run.__dict__[self.key] is None:
            run._fill_cost_stats()
        return run.__dict__[self.key]

    def __set__(self, run, value):
        run.__dict__[self.key] = value


@dataclasses.dataclass(frozen=True)
class FilterSource:
    """What a run's deferred cost statistics read: the relation's name,
    its filter's conjuncts, and the host columns those conjuncts read as
    the query saw them (``publish`` re-points ``PimDatabase.tables`` at
    fresh arrays, so the references stay a consistent snapshot)."""
    rel: str
    conjuncts: Tuple
    columns: Dict[str, np.ndarray]


@dataclasses.dataclass
class RelationRun:
    """Per-relation outcome of a query.

    The ``agg_plane_reads*`` counters come from the fused executor's
    reduce plan: aggregate-plane tile reads per pass with grouped
    popcounts vs one read per ReduceSum/MinMax (the pre-grouping
    executor) — zero on eager/baseline runs, which have no plan.

    ``selectivity`` (the mask's pass fraction) and ``filter_attr_sels``
    (each filter conjunct's pass fraction, by a NumPy scan of the host
    columns) feed only :func:`cost_report`.  Left ``None``, they are
    computed from ``filter_source`` on first read, so serving a query
    never pays for them.
    """
    n_records: int
    mask: np.ndarray
    trace: List[isa.PimInstruction]
    filter_attr_bits: List[int]
    agg_attr_bits: List[int]
    agg_plane_reads: int = 0
    agg_plane_reads_ungrouped: int = 0
    n_reduce_jobs: int = 0
    selectivity: float = _CostStat()
    filter_attr_sels: List[float] = _CostStat()
    filter_source: Optional[FilterSource] = dataclasses.field(
        default=None, repr=False)

    def _fill_cost_stats(self) -> None:
        src = self.filter_source
        if src is None:
            raise ValueError("RelationRun has neither its cost statistics "
                             "nor a filter_source to compute them from")
        with obs.span("relation_stats", rel=src.rel,
                      conjuncts=len(src.conjuncts)):
            if self.__dict__["_filter_attr_sels"] is None:
                self.filter_attr_sels = _conjunct_selectivities(
                    src.columns, src.conjuncts)
            if self.__dict__["_selectivity"] is None:
                mask = self.mask
                self.selectivity = float(mask.mean()) if mask.size else 0.0
        self.filter_source = None        # release the columns


class Engine(enum.Enum):
    """Execution substrate of :meth:`PimDatabase.execute`.

    FUSED — one compiled jax dispatch per relation program (the paper's
    single-readout model; cross-query linked for multi-spec batches).
    EAGER — the instruction-at-a-time PIM engine, the bit-level oracle.
    ORACLE — the numpy column-store scan baseline (paper §5.5).
    """
    FUSED = "fused"
    EAGER = "eager"
    ORACLE = "oracle"

    @classmethod
    def coerce(cls, v) -> "Engine":
        """Accept an Engine, its string value, or a legacy ``fused=``
        bool (True -> FUSED, False -> EAGER)."""
        if isinstance(v, Engine):
            return v
        if isinstance(v, str):
            return cls(v.lower())
        return cls.FUSED if v else cls.EAGER


# Result columns that are derived money at cents x percent scale.
_REVENUE_COLS = {"revenue", "promo_revenue"}


@dataclasses.dataclass
class QueryResult:
    """Uniform result of :meth:`PimDatabase.execute` — every field is
    present on every (engine, spec) combination, with consistent names.

    Mask/aggregate scope (``spec.host is None``): ``aggregates``
    (group -> {agg: value}) and ``relations`` are populated and
    ``columns``/``rows`` are empty.  End-to-end scope: ``columns`` /
    ``rows`` / ``materialized_rows`` hold the host stage's full result
    table — ``rows`` are the exact PIM-encoded integers (``None`` for
    empty min/max/avg) the oracle comparison uses, ``decoded_rows()``
    applies the schema's presentation decoding (currency, ISO dates,
    dictionary strings).  ``batch_stats`` is the dispatch-level
    accounting of the batch this query ran in (shared by every member of
    one ``execute(list)`` call); ``cached`` is set by the serving layer
    when the result came from its version-keyed cache.
    """
    spec: Q.QuerySpec
    engine: Engine = Engine.FUSED
    aggregates: Dict[str, Dict[str, object]] = dataclasses.field(
        default_factory=dict)
    relations: Dict[str, RelationRun] = dataclasses.field(
        default_factory=dict)
    columns: Tuple[str, ...] = ()
    rows: List[tuple] = dataclasses.field(default_factory=list)
    pim_s: float = 0.0
    host_s: float = 0.0
    wall_s: float = 0.0
    materialized_rows: Dict[str, int] = dataclasses.field(
        default_factory=dict)
    batch_stats: Optional[Dict[str, object]] = None
    cached: bool = False

    @property
    def name(self) -> str:
        return self.spec.name

    @property
    def kind(self) -> str:
        return getattr(self.spec, "kind", "")

    @property
    def wall_time_s(self) -> float:
        return self.wall_s

    @classmethod
    def from_table(cls, spec, table: "E.HostTable", pim_s: float,
                   host_s: float, mat_rows: Dict[str, int],
                   engine: Engine = Engine.FUSED,
                   batch_stats: Optional[Dict[str, object]] = None
                   ) -> "QueryResult":
        cols, rows = _table_rows(table)
        return cls(spec=spec, engine=engine, columns=cols, rows=rows,
                   pim_s=pim_s, host_s=host_s, wall_s=pim_s + host_s,
                   materialized_rows=dict(mat_rows),
                   batch_stats=batch_stats)

    def decoded_rows(self) -> List[tuple]:
        out = []
        for row in self.rows:
            dec = []
            for c, v in zip(self.columns, row):
                if v is None:
                    dec.append(None)
                elif c in _REVENUE_COLS:
                    dec.append(S.decode_revenue(v))
                else:
                    dec.append(S.decode_value(c, v))
            out.append(tuple(dec))
        return out

    @property
    def total_materialized(self) -> int:
        return sum(self.materialized_rows.values())


# Legacy name: the old mask/aggregate-scope result type. Unified now.
QueryRun = QueryResult


def _table_rows(table: "E.HostTable") -> Tuple[Tuple[str, ...], List[tuple]]:
    def cell(v):
        if v is None:
            return None
        if isinstance(v, (float, np.floating)):   # host-stage avg
            return float(v)
        return int(v)

    cols = tuple(table.columns)
    rows = [tuple(cell(table.columns[c][i]) for c in cols)
            for i in range(table.n_rows)]
    return cols, rows


@dataclasses.dataclass
class PendingQuery:
    """Split-phase handle between :meth:`PimDatabase.dispatch_batch` and
    :meth:`PimDatabase.finish_query`: the array stage has run (masks,
    aggregates, materialized columns demuxed); the host stage — if the
    spec has one — has not."""
    spec: Q.QuerySpec
    engine: Engine
    result: Optional[QueryResult] = None    # complete already (no host)
    host: Optional[object] = None           # E.HostStage still to run
    materialized: Dict[str, "E.HostTable"] = dataclasses.field(
        default_factory=dict)
    mat_rows: Dict[str, int] = dataclasses.field(default_factory=dict)
    pim_s: float = 0.0
    batch_stats: Optional[Dict[str, object]] = None

    @property
    def needs_host(self) -> bool:
        return self.result is None


@dataclasses.dataclass
class _BatchRelation:
    """One (query, relation) program's wiring inside a linked batch."""
    rel_name: str
    pred: object                            # None for scan-all stages
    compiler: Compiler
    mask_reg: str
    group_regs: List[Tuple[str, Dict]]
    mat_reg: Optional[str]
    slot: int                               # index into the relation's slots


@dataclasses.dataclass
class _BatchQuery:
    """Per-query compile product of ``PimDatabase._compile_batch``."""
    spec: Q.QuerySpec
    host: Optional[object]                  # E.HostStage when end-to-end
    rels: List[_BatchRelation]


class PimDatabase:
    """``mesh``: a ``jax.sharding.Mesh`` — every PIM-resident relation is
    sharded along the record/word axis over ``shard_axes`` (default: all
    mesh axes) and the fused path runs SPMD via shard_map, one logical
    dispatch per relation (see ``core.distributed``)."""

    def __init__(self, tables: Dict[str, Dict[str, np.ndarray]],
                 backend: str = "jnp", mesh=None, shard_axes=None,
                 wear_policy: str = "rotate"):
        self.tables = tables
        self.backend = backend
        self.mesh = mesh
        # DML write path: slot-allocation policy for append segments
        # ("rotate" = wear-leveled, "first_fit" = the unleveled strawman)
        # and lazily-built per-relation mutable state (repro.dml).
        self.wear_policy = wear_policy
        self._dml: Dict[str, object] = {}
        if mesh is not None:
            from repro.core import distributed as dist
            self.shard_axes = dist.mesh_shard_axes(mesh, shard_axes)
        else:
            self.shard_axes = None
        # Counters of the most recent FUSED execute() call (dispatches,
        # plane reads, link dedup, walls) — None until one has run.
        self.last_batch_stats: Optional[Dict[str, object]] = None
        self.relations: Dict[str, eng.PimRelation] = {}
        for name, cols in tables.items():
            if S.SCHEMA[name].in_pim:
                enc = {a.name: a.encoding for a in S.SCHEMA[name].attrs}
                rel = eng.PimRelation.from_columns(name, cols, encodings=enc)
                if mesh is not None:
                    rel = rel.shard(mesh, self.shard_axes)
                self.relations[name] = rel

    # -- PIM execution ------------------------------------------------------
    def _compile_relation(self, rel: eng.PimRelation, spec: Q.QuerySpec,
                          pred, namespace: str = ""
                          ) -> Tuple[Compiler, str, List[Tuple[str, Dict]]]:
        """Compile the FULL program for one relation: filter, group masks,
        aggregates. Returns (compiler, filter mask register,
        [(group label, {agg name: (kind, reg)})])."""
        with obs.span("compile", rel=rel.name) as sp:
            c = Compiler(rel, namespace=namespace)
            is_agg_rel = (spec.kind == "full"
                          and rel.name == spec.agg_relation)
            mask_reg = c.compile_filter(pred, with_transform=not is_agg_rel)
            group_regs: List[Tuple[str, Dict]] = []
            if is_agg_rel:
                for label, gpred in (spec.groups or [("all", None)]):
                    if gpred is None:
                        gmask = mask_reg
                    else:
                        gm = c.compile_pred(gpred)
                        gmask = c.fresh("m")
                        c.program.append(isa.BitwiseAnd(
                            dest=gmask, src_a=mask_reg, src_b=gm))
                    group_regs.append((label, c.compile_aggregates(
                        gmask, spec.aggregates)))
            sp.set_metadata(instrs=len(c.program))
        return c, mask_reg, group_regs

    @staticmethod
    def _compile_materialize(rel: eng.PimRelation, pred, cols,
                             namespace: str = ""
                             ) -> Tuple[Compiler, str, str]:
        """Compile one relation's filter+materialize program for a host
        stage's scan (a scan-all mask where ``pred`` is None). Returns
        (compiler, filter mask register, materialize register)."""
        with obs.span("compile", rel=rel.name) as sp:
            c = Compiler(rel, namespace=namespace)
            mask_reg = (c.compile_filter(pred, with_transform=False)
                        if pred is not None else c.compile_scan_all())
            mat_reg = c.compile_materialize(mask_reg, cols)
            sp.set_metadata(instrs=len(c.program))
        return c, mask_reg, mat_reg

    @staticmethod
    def _finalize_aggs(group_regs, read_scalar, read_reduce) -> Dict[str, Dict[str, object]]:
        aggs: Dict[str, Dict[str, object]] = {}
        for label, regs in group_regs:
            out: Dict[str, object] = {}
            for name, (kind, reg) in regs.items():
                if kind == "avg_pair":
                    s_reg, c_reg = reg.split("/")
                    s, c = int(read_scalar(s_reg)), int(read_scalar(c_reg))
                    # Empty-group avg is None on every path (eager, fused,
                    # distributed, baseline) — never a 0/0 pair that turns
                    # into a ZeroDivisionError or NaN downstream.
                    out[name] = None if c == 0 else (s, c)
                elif kind == "minmax":
                    out[name] = read_reduce(reg)
                else:
                    out[name] = read_scalar(reg)
            aggs[label] = out
        return aggs

    def _relation_run(self, rel: eng.PimRelation, rel_name: str,
                      spec: Q.QuerySpec, pred, mask: np.ndarray,
                      trace: List[isa.PimInstruction],
                      cp: Optional[prog.CompiledProgram] = None
                      ) -> RelationRun:
        cols = self.tables[rel_name]
        attrs = predicate_attrs(pred)
        source = FilterSource(rel_name, tuple(_conjuncts(pred)),
                              {a: cols[a] for a in attrs if a in cols})
        agg_bits: List[int] = []
        if spec.kind == "full" and rel_name == spec.agg_relation:
            for a in spec.aggregates:
                if a.expr is not None:
                    agg_bits += [rel.width_of(x)
                                 for x in predicate_attrs_of_expr(a.expr)]
        return RelationRun(
            n_records=rel.n_records, mask=mask, trace=trace,
            filter_attr_bits=[rel.width_of(a) for a in attrs],
            agg_attr_bits=agg_bits, filter_source=source,
            agg_plane_reads=cp.agg_plane_reads if cp else 0,
            agg_plane_reads_ungrouped=(cp.agg_plane_reads_ungrouped
                                       if cp else 0),
            n_reduce_jobs=cp.n_reduce_jobs if cp else 0)

    # -- unified execution entry point --------------------------------------
    def execute(self, spec_or_specs: Union[Q.QuerySpec, Sequence[Q.QuerySpec]],
                *, engine: Union[Engine, str, bool] = Engine.FUSED
                ) -> Union[QueryResult, List[QueryResult]]:
        """THE query entry point.  A single :class:`~repro.db.queries.
        QuerySpec` returns one :class:`QueryResult`; a sequence returns
        one result per spec in batch order.  ``engine`` selects the
        substrate (:class:`Engine`; a string value or legacy ``fused=``
        bool is coerced).

        Multi-spec FUSED batches are cross-query fused — linked into ONE
        SSA program per relation and dispatched once per relation, so N
        queries over ``lineitem`` stream its bit-planes once, not N
        times.  An empty sequence returns ``[]`` and a one-element
        sequence takes the direct single-query path — neither triggers
        the link/dispatch machinery.  Every value is bit-identical
        across engines and batch shapes.  Batch-level counters land in
        ``self.last_batch_stats`` (FUSED only).
        """
        engine = Engine.coerce(engine)
        if isinstance(spec_or_specs, Q.QuerySpec):
            return self._execute_one(spec_or_specs, engine)
        specs = list(spec_or_specs)
        if not specs:
            # Nothing to link or dispatch; clear stale batch counters so
            # callers never attribute a previous batch to this one.
            self.last_batch_stats = _empty_batch_stats()
            return []
        if len(specs) == 1 or engine is not Engine.FUSED:
            return [self._execute_one(s, engine) for s in specs]
        with obs.span("execute", q=_names(specs)):
            pendings, _ = self.dispatch_batch(specs)
            return [self.finish_query(p) for p in pendings]

    def _execute_one(self, spec: Q.QuerySpec, engine: Engine) -> QueryResult:
        with obs.query(spec.name), obs.span("execute"):
            if engine is Engine.ORACLE:
                return self._execute_baseline(spec)
            if spec.host is not None:
                return self._execute_host(spec, engine)
            return self._execute_pim(spec, engine)

    def _execute_pim(self, spec: Q.QuerySpec, engine: Engine) -> QueryResult:
        """Mask/aggregate-scope execution on the PIM copy.

        FUSED: one compiled dispatch per relation program — the paper's
        single-pass/single-readout execution model.  With a ``mesh`` the
        dispatch is the shard_map-wrapped SPMD executable (still one
        logical dispatch; see ``core.distributed``).  EAGER: the
        instruction-at-a-time engine (oracle) — also correct on sharded
        relations, via global ops.
        """
        t_all = time.perf_counter()
        fused = engine is Engine.FUSED
        rel_runs: Dict[str, RelationRun] = {}
        aggs: Dict[str, Dict[str, object]] = {}
        rel_stats: Dict[str, Dict[str, object]] = {}
        pim_s = 0.0
        for rel_name, pred in spec.filters.items():
            rel = self.relations[rel_name]
            c, mask_reg, group_regs = self._compile_relation(rel, spec, pred)

            cp = None
            if fused:
                cp = prog.compile_program(rel, c.program,
                                          mask_outputs=(mask_reg,),
                                          backend=self.backend,
                                          mesh=self.mesh,
                                          shard_axes=self.shard_axes)
                t0 = time.perf_counter()
                res = prog.run_program(cp, rel)
                dt = time.perf_counter() - t0
                pim_s += dt
                with obs.span("unpack", rel=rel_name, records=rel.n_records):
                    if group_regs:
                        aggs.update(self._finalize_aggs(
                            group_regs, res.scalar, res.scalar))
                    mask = res.mask(mask_reg)
                rel_stats[rel_name] = _single_relation_stats(c, cp, dt)
            else:
                e = eng.Engine(rel, backend=self.backend)
                e.run(c.program)
                if group_regs:
                    aggs.update(self._finalize_aggs(
                        group_regs,
                        lambda r: int(e.read_scalar(r)), e.read_reduce))
                mask = e.read_mask(mask_reg)[: rel.n_records]

            rel_runs[rel_name] = self._relation_run(
                rel, rel_name, spec, pred, mask, list(c.program), cp=cp)
        wall = time.perf_counter() - t_all
        stats = None
        if fused:
            stats = _empty_batch_stats()
            stats.update(n_queries=1, n_dispatches=len(rel_stats),
                         pim_s=pim_s, wall_s=wall, relations=rel_stats)
            self.last_batch_stats = stats
        return QueryResult(spec=spec, engine=engine, aggregates=aggs,
                           relations=rel_runs, pim_s=pim_s, wall_s=wall,
                           batch_stats=stats)

    # -- end-to-end execution (PIM stage + host stage) -----------------------
    def _execute_host(self, spec: Q.QuerySpec, engine: Engine
                      ) -> QueryResult:
        """Execute a query END TO END: PIM filters + in-dispatch
        materialization hand the host only the selected records; the
        host stage (``db.exec``) joins, applies residual predicates,
        aggregates, and orders them into full TPC-H result rows.

        FUSED compiles each relation's filter+materialize program into
        one dispatch (sharded over the mesh when configured, masks and
        value buffers staying on-device/sharded); EAGER runs the
        instruction-at-a-time engine as the oracle path.
        """
        fused = engine is Engine.FUSED
        pim_stage, host = E.split_query(spec)
        t0 = time.perf_counter()
        materialized: Dict[str, E.HostTable] = {}
        mat_rows: Dict[str, int] = {}
        rel_stats: Dict[str, Dict[str, object]] = {}
        for rel_name, pred, cols in pim_stage:
            rel = self.relations[rel_name]
            c, _, mat_reg = self._compile_materialize(rel, pred, cols)
            if fused:
                cp = prog.compile_program(rel, c.program, mask_outputs=(),
                                          backend=self.backend,
                                          mesh=self.mesh,
                                          shard_axes=self.shard_axes)
                t1 = time.perf_counter()
                vals = prog.run_program(cp, rel).materialized(mat_reg)
                rel_stats[rel_name] = _single_relation_stats(
                    c, cp, time.perf_counter() - t1)
            else:
                e = eng.Engine(rel, backend=self.backend)
                e.run(c.program)
                vals = e.read_materialized(mat_reg)
            materialized[rel_name] = E.HostTable(
                {a: np.asarray(v, np.int64) for a, v in vals.items()})
            mat_rows[rel_name] = materialized[rel_name].n_rows
        pim_s = time.perf_counter() - t0

        t0 = time.perf_counter()
        table = self._host_stage(host, materialized, mat_rows)
        host_s = time.perf_counter() - t0
        stats = None
        if fused:
            stats = _empty_batch_stats()
            stats.update(n_queries=1, n_dispatches=len(rel_stats),
                         pim_s=sum(s["pim_s"] for s in rel_stats.values()),
                         host_s=host_s, wall_s=pim_s + host_s,
                         relations=rel_stats)
            self.last_batch_stats = stats
        return QueryResult.from_table(spec, table, pim_s, host_s, mat_rows,
                                      engine=engine, batch_stats=stats)

    # -- batched execution (cross-query fusion) ------------------------------
    def _compile_batch(self, specs) -> Tuple[
            List[_BatchQuery], Dict[str, List[Tuple[tuple, tuple]]]]:
        """Compile every spec's per-relation program — each under its own
        ``q<i>.`` register namespace — and group the programs by relation
        for linking. Returns (per-query wiring, {relation: [(instrs,
        mask_outputs)] in slot order})."""
        works: List[_BatchQuery] = []
        rel_programs: Dict[str, List[Tuple[tuple, tuple]]] = {}
        for qi, spec in enumerate(specs):
            with obs.query(spec.name):
                ns = f"q{qi}."
                rels: List[_BatchRelation] = []
                if spec.host is not None:
                    pim_stage, host = E.split_query(spec)
                    for rel_name, pred, cols in pim_stage:
                        rel = self.relations[rel_name]
                        c, mask_reg, mat_reg = self._compile_materialize(
                            rel, pred, cols, namespace=ns)
                        progs = rel_programs.setdefault(rel_name, [])
                        rels.append(_BatchRelation(rel_name, pred, c, mask_reg,
                                                   [], mat_reg, len(progs)))
                        progs.append((tuple(c.program), ()))
                    works.append(_BatchQuery(spec, host, rels))
                else:
                    for rel_name, pred in spec.filters.items():
                        rel = self.relations[rel_name]
                        c, mask_reg, group_regs = self._compile_relation(
                            rel, spec, pred, namespace=ns)
                        progs = rel_programs.setdefault(rel_name, [])
                        rels.append(_BatchRelation(rel_name, pred, c, mask_reg,
                                                   group_regs, None, len(progs)))
                        progs.append((tuple(c.program), (mask_reg,)))
                    works.append(_BatchQuery(spec, None, rels))
        return works, rel_programs

    def dispatch_batch(self, specs: Sequence[Q.QuerySpec]
                       ) -> Tuple[List[PendingQuery], Dict[str, object]]:
        """Array stage of a cross-query FUSED batch: specs are compiled
        independently (canonicalized, namespaced), grouped by relation,
        linked into ONE SSA program per relation
        (``core.program.link_programs`` dedups shared subexpressions),
        and dispatched ONCE per relation — N queries over ``lineitem``
        stream its bit-planes once, not N times.  Per-query outputs are
        demuxed through the linked program's ``query_slots``.

        Host stages are NOT run here: each returned :class:`PendingQuery`
        either already carries its complete :class:`QueryResult`
        (mask/aggregate specs) or holds the demuxed host tables for
        :meth:`finish_query` — so a serving layer can drain host stages
        on a worker pool while the next admission window dispatches.

        Linking is deterministic, so a recurring batch produces the same
        linked instruction stream and hits the compiled-executable
        ``LruFnCache``.  Batch-level counters (dispatches, plane reads,
        dedup, linked cache keys, walls) land in
        ``self.last_batch_stats`` and are returned.
        """
        with obs.query(_names(specs)):
            return self._dispatch_linked(specs)

    def _dispatch_linked(self, specs: Sequence[Q.QuerySpec]
                         ) -> Tuple[List[PendingQuery], Dict[str, object]]:
        t_all = time.perf_counter()
        works, rel_programs = self._compile_batch(specs)

        compiled: Dict[str, prog.CompiledProgram] = {}
        results: Dict[str, prog.ProgramResult] = {}
        linked: Dict[str, prog.LinkedProgram] = {}
        pim_wall: Dict[str, float] = {}
        for rel_name, programs in rel_programs.items():
            rel = self.relations[rel_name]
            with obs.span("link", rel=rel_name,
                          programs=len(programs)) as sp:
                lp = prog.link_programs(programs, relation=rel)
                sp.set_metadata(deduped=lp.n_deduped)
            cp = prog.compile_program(
                rel, lp.instrs, mask_outputs=lp.mask_outputs,
                backend=self.backend, mesh=self.mesh,
                shard_axes=self.shard_axes, query_slots=lp.slots)
            t0 = time.perf_counter()
            res = prog.run_program(cp, rel)
            pim_wall[rel_name] = time.perf_counter() - t0
            compiled[rel_name], results[rel_name] = cp, res
            linked[rel_name] = lp

        # Attribute each relation's single dispatch evenly to the queries
        # that share it (the point of fusion: the dispatch is shared).
        n_users: Dict[str, int] = {}
        for w in works:
            for br in w.rels:
                n_users[br.rel_name] = n_users.get(br.rel_name, 0) + 1
        share = {r: pim_wall[r] / n_users[r] for r in pim_wall}

        stats: Dict[str, object] = {
            "n_queries": len(works),
            "n_dispatches": len(rel_programs),
            "pim_s": sum(pim_wall.values()),
            "demux_s": 0.0,
            "wall_s": 0.0,
            "relations": {
                r: {"n_programs": len(rel_programs[r]),
                    "instrs_unlinked": linked[r].n_instrs_unlinked,
                    "instrs_linked": len(linked[r].instrs),
                    "instrs_deduped": linked[r].n_deduped,
                    "plane_reads": compiled[r].total_plane_reads,
                    "agg_plane_reads": compiled[r].agg_plane_reads,
                    "source_plane_reads": compiled[r].source_plane_reads,
                    "linked_key": linked[r].cache_key,
                    "pim_s": pim_wall[r]}
                for r in rel_programs},
        }

        pendings: List[PendingQuery] = []
        demux_s = 0.0
        with obs.span("demux", queries=len(works)):
            for w in works:
                t0 = time.perf_counter()
                if w.host is not None:
                    materialized: Dict[str, E.HostTable] = {}
                    mat_rows: Dict[str, int] = {}
                    pim_s = 0.0
                    for br in w.rels:
                        view = results[br.rel_name].query(br.slot)
                        vals = view.materialized(br.mat_reg)
                        materialized[br.rel_name] = E.HostTable(
                            {a: np.asarray(v, np.int64)
                             for a, v in vals.items()})
                        mat_rows[br.rel_name] = materialized[br.rel_name].n_rows
                        pim_s += share[br.rel_name]
                    pendings.append(PendingQuery(
                        w.spec, Engine.FUSED, host=w.host,
                        materialized=materialized, mat_rows=mat_rows,
                        pim_s=pim_s, batch_stats=stats))
                else:
                    rel_runs: Dict[str, RelationRun] = {}
                    aggs: Dict[str, Dict[str, object]] = {}
                    wall = 0.0
                    for br in w.rels:
                        view = results[br.rel_name].query(br.slot)
                        rel = self.relations[br.rel_name]
                        with obs.span("unpack", rel=br.rel_name,
                                      records=rel.n_records):
                            mask = view.mask(br.mask_reg)
                            if br.group_regs:
                                aggs.update(self._finalize_aggs(
                                    br.group_regs, view.scalar, view.scalar))
                        rel_runs[br.rel_name] = self._relation_run(
                            rel, br.rel_name, w.spec, br.pred, mask,
                            list(br.compiler.program),
                            cp=compiled[br.rel_name])
                        wall += share[br.rel_name]
                    res = QueryResult(
                        spec=w.spec, engine=Engine.FUSED, aggregates=aggs,
                        relations=rel_runs, pim_s=wall,
                        wall_s=wall + time.perf_counter() - t0,
                        batch_stats=stats)
                    pendings.append(PendingQuery(w.spec, Engine.FUSED,
                                                 result=res, pim_s=wall,
                                                 batch_stats=stats))
                demux_s += time.perf_counter() - t0

        stats["demux_s"] = demux_s
        stats["wall_s"] = time.perf_counter() - t_all
        self.last_batch_stats = stats
        return pendings, stats

    def finish_query(self, pending: PendingQuery) -> QueryResult:
        """Host stage of one :meth:`dispatch_batch` query.  No-op for
        mask/aggregate specs (result already complete).  Thread-safe:
        the serving layer calls this from a worker pool."""
        if pending.result is not None:
            return pending.result
        t0 = time.perf_counter()
        with obs.query(pending.spec.name):
            table = self._host_stage(pending.host, pending.materialized,
                                     pending.mat_rows)
        host_s = time.perf_counter() - t0
        return QueryResult.from_table(
            pending.spec, table, pending.pim_s, host_s, pending.mat_rows,
            engine=pending.engine, batch_stats=pending.batch_stats)

    def _host_stage(self, host, materialized: Dict[str, "E.HostTable"],
                    mat_rows: Dict[str, int]) -> "E.HostTable":
        with obs.span("host_stage", rows_in=sum(mat_rows.values())) as sp:
            table = E.run_host_stage(host, E.ExecContext(materialized,
                                                         self.tables))
            sp.set_metadata(rows_out=table.n_rows)
        return table

    # -- baseline (numpy scan oracle) ----------------------------------------
    def _execute_baseline(self, spec: Q.QuerySpec) -> QueryResult:
        """The paper's §5.5 in-memory column-store scan.  For specs with
        a host stage the filter masks come from the same numpy scans
        (``exec.baseline_context``) and the host stage runs over them —
        full result rows, zero PIM involvement."""
        t_all = time.perf_counter()
        rel_runs: Dict[str, RelationRun] = {}
        aggs: Dict[str, Dict[str, object]] = {}
        for rel_name, pred in spec.filters.items():
            cols = self.tables[rel_name]
            n = len(next(iter(cols.values())))
            mask = Q.eval_pred(cols, pred)
            if spec.kind == "full" and rel_name == spec.agg_relation:
                for label, gpred in (spec.groups or [("all", None)]):
                    gmask = mask if gpred is None else (mask & Q.eval_pred(cols, gpred))
                    aggs[label] = {a.name: Q.eval_aggregate(cols, gmask, a)
                                   for a in spec.aggregates}
            rel_runs[rel_name] = RelationRun(
                n_records=n, mask=mask, trace=[],
                selectivity=float(mask.mean()) if mask.size else 0.0,
                filter_attr_bits=[], filter_attr_sels=[], agg_attr_bits=[])
        columns: Tuple[str, ...] = ()
        rows: List[tuple] = []
        mat_rows: Dict[str, int] = {}
        host_s = 0.0
        if spec.host is not None:
            t0 = time.perf_counter()
            ctx = E.baseline_context(self.tables, spec)
            table = E.run_host_stage(spec.host, ctx)
            host_s = time.perf_counter() - t0
            columns, rows = _table_rows(table)
            mat_rows = {r: t.n_rows for r, t in ctx.materialized.items()}
        return QueryResult(spec=spec, engine=Engine.ORACLE,
                           aggregates=aggs, relations=rel_runs,
                           columns=columns, rows=rows, host_s=host_s,
                           wall_s=time.perf_counter() - t_all,
                           materialized_rows=mat_rows)

    # -- DML (repro.dml): mutable relations ----------------------------------
    def dml_state(self, rel_name: str):
        """The lazily-built :class:`repro.dml.RelationDml` of one
        PIM-resident relation (created on first use; the relation handle
        is republished with its append-segment capacity pinned, which
        keeps ``layout.n_words`` — and thus every compiled-executable
        signature — stable across within-capacity inserts)."""
        from repro import dml as dml_mod     # lazy: dml imports repro.db
        d = self._dml.get(rel_name)
        if d is None:
            if rel_name not in self.relations:
                raise KeyError(f"{rel_name!r} is not PIM-resident")
            d = dml_mod.RelationDml(self.relations[rel_name],
                                    self.tables[rel_name],
                                    policy=self.wear_policy)
            self.relations[rel_name] = d.rel
            self._dml[rel_name] = d
        return d

    def apply(self, mutations: Sequence[object]) -> Dict[str, Dict[str, object]]:
        """Apply a DML batch (``repro.dml`` Insert/Delete/Update/Compact
        specs) in order and publish the mutated relations.

        Publishing bumps each mutated relation's content version ONCE
        per batch — serving-layer result caches key on versions, so any
        cached result computed against pre-mutation contents misses from
        then on by construction.  ``self.tables`` is re-pointed at the
        live rows (logical-id order), keeping the numpy oracle/baseline
        path in lock-step; the dict itself is shallow-copied first
        because test fixtures share one tables dict across PimDatabase
        instances.  With a ``mesh``, mutated relations are re-sharded
        before publishing.  Returns per-relation accounting.
        """
        from repro import dml as dml_mod
        stats: Dict[str, Dict[str, object]] = {}
        order: List[str] = []
        for m in mutations:
            name = dml_mod.mutation_relation(m)
            with obs.span("dml.mutate", rel=name) as sp:
                st = self.dml_state(name).apply(m)
                sp.set_metadata(rows=st.n_rows,
                                cells_written=st.cells_written)
            entry = stats.setdefault(name, {
                "n_mutations": 0, "n_rows": 0, "n_instructions": 0,
                "cycles": 0, "cells_written": 0})
            entry["n_mutations"] += 1
            entry["n_rows"] += st.n_rows
            entry["n_instructions"] += st.n_instructions
            entry["cycles"] += st.cycles
            entry["cells_written"] += st.cells_written
            if name not in order:
                order.append(name)
        versions = self.publish(order)
        for name in order:
            d = self._dml[name]
            entry = stats[name]
            entry["version"] = versions[name]
            entry["busiest_row_ops"] = d.segments.busiest_row_ops()
            entry["capacity_records"] = d.capacity
        return stats

    def publish(self, rel_names: Sequence[str]) -> Dict[str, int]:
        """Publish the current DML state of each named relation: bump
        the content version (version-keyed serving caches miss from then
        on by construction), re-shard if a mesh is attached, and
        re-point ``self.tables`` at the live rows.  Shared by
        :meth:`apply` and the fault-recovery layer
        (``repro.faults.FaultManager.scrub`` republishes repaired
        relations through this exact path, so a repair can never leave a
        stale cached result servable).  Returns ``{name: new_version}``.
        """
        self.tables = dict(self.tables)
        versions: Dict[str, int] = {}
        for name in rel_names:
            d = self._dml[name]
            with obs.span("dml.publish", rel=name, rows=len(d.slot_of)):
                version = max(d.rel.version,
                              self.relations[name].version) + 1
                rel = dataclasses.replace(d.rel, version=version)
                if self.mesh is not None:
                    rel = rel.shard(self.mesh, self.shard_axes)
                self.relations[name] = rel
                d.rel = rel
                self.tables[name] = d.live_columns()
                versions[name] = version
        return versions

    def dml_row_ops(self) -> Dict[str, float]:
        """Accumulated busiest-row DML cell writes per mutated relation
        (the §6.4 write pressure ``cost_report`` folds into endurance)."""
        return {name: d.segments.busiest_row_ops()
                for name, d in self._dml.items()}

    def report(self, run: "QueryRun", sf_scale: float = 1.0,
               hw: cm.HwParams = cm.DEFAULT_HW) -> "QueryCostReport":
        """:func:`cost_report` wired to THIS database's state: resident/
        reserved plane bytes and accumulated DML write pressure included."""
        return cost_report(run, sf_scale, hw, relations=self.relations,
                           dml_row_ops=self.dml_row_ops())

    # -- relation versioning -------------------------------------------------
    def bump_version(self, rel_name: str) -> int:
        """Advance a relation's monotonic content version (the
        publish-after-mutate hook; today's mutations are test reloads,
        the ROADMAP HTAP write path will call this).  Version-keyed
        result caches (``repro.serve``) miss from then on by
        construction.  Returns the new version."""
        rel = self.relations[rel_name].bumped()
        self.relations[rel_name] = rel
        return rel.version

    # -- deprecated shims ----------------------------------------------------
    def run_pim(self, spec: Q.QuerySpec, fused: bool = True) -> QueryResult:
        """Deprecated: use ``execute(spec.filter_only(), engine=...)``."""
        warnings.warn(
            "PimDatabase.run_pim is deprecated; use "
            "execute(spec.filter_only(), engine=Engine.FUSED/EAGER)",
            DeprecationWarning, stacklevel=2)
        return self.execute(spec.filter_only(), engine=Engine.coerce(fused))

    def run_query(self, spec: Q.QuerySpec, fused: bool = True
                  ) -> QueryResult:
        """Deprecated: use ``execute(spec, engine=...)``."""
        warnings.warn(
            "PimDatabase.run_query is deprecated; use "
            "execute(spec, engine=Engine.FUSED/EAGER)",
            DeprecationWarning, stacklevel=2)
        return self.execute(spec, engine=Engine.coerce(fused))

    def run_queries(self, specs, fused: bool = True) -> List[QueryResult]:
        """Deprecated: use ``execute(list_of_specs, engine=...)``."""
        warnings.warn(
            "PimDatabase.run_queries is deprecated; use "
            "execute(specs, engine=Engine.FUSED/EAGER)",
            DeprecationWarning, stacklevel=2)
        return self.execute(list(specs), engine=Engine.coerce(fused))

    def run_baseline(self, spec: Q.QuerySpec) -> QueryResult:
        """Numpy column-scan oracle at the spec's filter scope —
        equivalent to ``execute(spec.filter_only(), engine=Engine.
        ORACLE)`` (kept un-deprecated: it is the oracle the tests pin
        results against)."""
        return self._execute_baseline(spec.filter_only())


def _names(specs: Sequence[Q.QuerySpec]) -> str:
    return "+".join(s.name for s in specs)


def _empty_batch_stats() -> Dict[str, object]:
    return {"n_queries": 0, "n_dispatches": 0, "pim_s": 0.0,
            "demux_s": 0.0, "host_s": 0.0, "wall_s": 0.0, "relations": {}}


def _single_relation_stats(c: Compiler, cp: prog.CompiledProgram,
                           pim_s: float) -> Dict[str, object]:
    """Per-relation stats of an unlinked single-query dispatch, shaped
    like the linked-batch entries (zero dedup, one program)."""
    n = len(c.program)
    return {"n_programs": 1, "instrs_unlinked": n, "instrs_linked": n,
            "instrs_deduped": 0,
            "plane_reads": cp.total_plane_reads,
            "agg_plane_reads": cp.agg_plane_reads,
            "source_plane_reads": cp.source_plane_reads,
            "linked_key": None, "pim_s": pim_s}


def avg_value(pair) -> Optional[float]:
    """Finalize an exact avg (sum, count) pair into a float; an empty
    group (already ``None`` from ``_finalize_aggs``/``eval_aggregate``)
    stays ``None`` — never a ZeroDivisionError or NaN."""
    if pair is None:
        return None
    s, c = pair
    return s / c


def predicate_attrs_of_expr(e) -> List[str]:
    from .compiler import Col, Mul, AddE, RSubImm, Lit
    out: List[str] = []

    def walk(x):
        if isinstance(x, Col):
            out.append(x.name)
        elif isinstance(x, (Mul, AddE)):
            walk(x.a)
            if not isinstance(x.b, Lit):
                walk(x.b)
        elif isinstance(x, RSubImm):
            walk(x.e)

    walk(e)
    seen, res = set(), []
    for a in out:
        if a not in seen:
            seen.add(a)
            res.append(a)
    return res


def _conjuncts(pred) -> list:
    return list(pred.ps) if isinstance(pred, And) else [pred]


def _conjunct_selectivities(cols, conjs) -> List[float]:
    """Per-conjunct pass fractions in evaluation order (baseline model)."""
    sels = []
    for c in conjs:
        try:
            sels.append(float(Q.eval_pred(cols, c).mean()))
        except Exception:
            sels.append(1.0)
    return sels


# --------------------------------------------------------------------------
# Paper-scale cost report (the gem5 stand-in)
# --------------------------------------------------------------------------
@dataclasses.dataclass
class QueryCostReport:
    name: str
    kind: str
    cycles: Dict[str, int]
    pim_time_s: float
    read_time_s: float
    baseline_time_s: float
    speedup: float
    read_reduction: float
    energy_saving: float
    endurance_ops_per_cell_10y: float
    intermediate_cells: int
    # Memory accounting of the relations the query touched (0 when the
    # caller passes no relation handles): device-resident plane bytes —
    # every attribute plane PLUS the valid plane, spanning the FULL
    # reserved append-segment capacity — and the reserved-but-unused
    # share of that figure.
    bytes_resident: int = 0
    bytes_reserved: int = 0
    # Accumulated DML cell writes on the busiest row of those relations
    # (already folded into ``endurance_ops_per_cell_10y``).
    dml_row_ops: float = 0.0

    def row(self) -> str:
        return (f"{self.name},{self.kind},{self.cycles['total']},"
                f"{self.speedup:.2f},{self.read_reduction:.1f},"
                f"{self.energy_saving:.2f},{self.endurance_ops_per_cell_10y:.3g}")


def cost_report(run: QueryRun, sf_scale: float = 1.0,
                hw: cm.HwParams = cm.DEFAULT_HW, relations=None,
                dml_row_ops=None) -> QueryCostReport:
    """Project the measured run to paper scale (records x sf_scale vs the
    generated SF) and produce Fig. 8/11/15-comparable numbers.

    The PIM cycle count is size-independent (requests broadcast to all
    pages); read traffic and baseline scan traffic scale linearly with
    relation size — exactly the scaling the paper exploits.

    ``relations`` ({name: PimRelation}) adds resident/reserved plane
    bytes for the touched relations; ``dml_row_ops`` ({name: ops}) folds
    each relation's accumulated busiest-row DML cell writes into the
    endurance projection — ``PimDatabase.report`` passes both.
    """
    total = cm.ProgramCost()
    base_bytes = 0
    base_ops = 0.0
    pim_bytes = 0
    n_crossbars_busiest = 0
    exec_pages = 0
    trace_row_ops = 0.0
    bytes_resident = 0
    bytes_reserved = 0
    dml_ops = 0.0
    for rel_name, rr in run.relations.items():
        if relations is not None and rel_name in relations:
            bytes_resident += relations[rel_name].bytes_resident()
            bytes_reserved += relations[rel_name].bytes_reserved()
        if dml_row_ops is not None:
            dml_ops += float(dml_row_ops.get(rel_name, 0.0))
        n_scaled = int(rr.n_records * sf_scale)
        cost = cm.classify_program(rr.trace)
        for f in dataclasses.fields(cm.ProgramCost):
            setattr(total, f.name,
                    getattr(total, f.name) + getattr(cost, f.name))
        # Trace-derived §6.4 write pressure (per-instruction row_write_ops
        # sums), replacing the class-aggregate approximation below.
        trace_row_ops += analysis.write_profile(rr.trace).busiest_row_ops
        # baseline: scan predicate attrs (short-circuit + cacheline model),
        # then agg attrs for passing records
        sels = rr.filter_attr_sels or [1.0] * len(rr.filter_attr_bits)
        base_bytes += cm.baseline_scan_bytes(
            n_scaled, rr.filter_attr_bits, sels, hw)
        for bits in rr.agg_attr_bits:
            base_bytes += int(n_scaled * rr.selectivity * bits / 8)
        # host record-loop ops: SIMD-friendly predicate checks with
        # short-circuit, scalar dependent-chain aggregation arithmetic
        pass_frac = 1.0
        for s in sels:
            base_ops += 0.4 * n_scaled * pass_frac
            pass_frac *= s
        n_xbars = max(1, -(-n_scaled // 1024))
        exec_pages += max(1, n_xbars // 16384)
        if run.spec.kind == "full" and rel_name == run.spec.agg_relation:
            n_aggs = sum(2 if a.op == "avg" else 1
                         for a in run.spec.aggregates)
            n_groups = len(run.spec.groups or [1])
            n_mults = sum(1 for i in rr.trace if i.kind == "Multiply")
            base_ops += n_scaled * rr.selectivity * (
                6.0 * n_aggs + 3.0 * n_mults + 2.0)
            pim_bytes += cm.pim_read_bytes_aggregate(n_xbars,
                                                     n_aggs * n_groups)
        else:
            pim_bytes += cm.pim_read_bytes_filter(n_scaled)
        n_crossbars_busiest = max(n_crossbars_busiest, n_xbars)

    timing = cm.query_timing(total, 0, n_crossbars_busiest, base_bytes,
                             pim_bytes, n_modules=min(8, exec_pages),
                             baseline_ops=base_ops, hw=hw)
    energy = cm.query_energy(total, timing, n_crossbars_busiest, hw=hw)
    endurance = cm.endurance_ops_per_cell(
        total, exec_time_s=timing.pimdb_total_s, hw=hw,
        busiest_row_ops=trace_row_ops + dml_ops)
    return QueryCostReport(
        run.spec.name, run.spec.kind,
        dict(total=total.cycles_total, **total.breakdown()),
        timing.pim_time_s, timing.read_time_s, timing.baseline_time_s,
        timing.speedup, timing.read_reduction, energy.saving, endurance,
        total.intermediate_cells_peak,
        bytes_resident=bytes_resident, bytes_reserved=bytes_reserved,
        dml_row_ops=dml_ops)
