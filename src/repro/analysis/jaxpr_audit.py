"""Level 1: structural audit of the traced commit/replay/GC entrypoints.

Traces the real protocol entrypoints — ``si.run_round``,
``store.distributed_round``, ``wal.replay``, ``gc.gc_round`` — on tiny
deterministic fixtures and walks the resulting jaxprs (recursively through
``pjit`` / ``shard_map`` / ``scan`` / ``cond`` sub-jaxprs) checking the
invariants the AST lint can only approximate:

* **A1 (lock pairing)** — the commit path tags its CAS grant mask, release
  mask and commit decision with :func:`repro.core.annotations.tag`; a
  forward taint walk proves the grant mask flows into *both* the release
  tag (abort path) and the commit tag (whose install + visibility write
  consumes the lock). Taint is over-approximate (opaque calls pass it
  through), so a pairing failure is a real structural break, never an
  artifact of imprecision.
* **A2 (overflow-unsafe reductions)** — any ``reduce_sum``/``cumsum`` whose
  operand is timestamp-dtype (uint32) must originate from a bool conversion
  or the exact ⟨hi,lo⟩ base-2^16 digit split (``& 0xFFFF`` / ``>> 16``);
  ``reduce_min``/``reduce_max`` over uint32 must additionally be
  select/where-masked.
* **A3 (sentinel-blind selection)** — ``argmin``/``argmax`` operands must
  be boolean or select/where-masked; producer chains are resolved
  backwards through ``pjit`` (``jnp.where`` traces as a nested
  ``pjit[_where]``).
* **A4 (journal width)** — ``wal.append_intent``'s width guard raises at
  trace time; the audit converts that into a finding instead of a crash.

Findings map back to source via each equation's ``source_info`` and honor
the same ``# analysis: safe(...)`` comments as the AST lint.
"""
from __future__ import annotations

import dataclasses
from pathlib import Path
from typing import Callable, Dict, FrozenSet, List, Optional, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax._src import source_info_util
from jax.extend import core as jex_core

from repro import compat
from repro.analysis.rules import Finding, apply_suppressions
from repro.core import annotations as anno
from repro.core import gc as gc_ops
from repro.core import mvcc, si, store, wal
from repro.core.si import TxnBatch
from repro.core.tsoracle import VectorOracle, VectorState

Jaxpr, ClosedJaxpr = jex_core.Jaxpr, jex_core.ClosedJaxpr
Var, Literal = jex_core.Var, jex_core.Literal

TS_DTYPE = np.dtype(np.uint32)

# shape/layout-only primitives: the producer classification looks through
# them at operand 0
_PASSTHRU = {"broadcast_in_dim", "reshape", "squeeze", "transpose", "slice",
             "rev", "copy", "reduce_precision", "stop_gradient", "name"}
_CALL_PRIMS = {"pjit", "closed_call", "core_call", "remat", "custom_jvp_call",
               "custom_vjp_call"}


def _frame(eqn) -> Tuple[str, int]:
    """(file, line) of the first user frame — skipping annotations.py, where
    every ``tag()`` call would otherwise be attributed."""
    try:
        for fr in source_info_util.user_frames(eqn.source_info):
            if not fr.file_name.endswith("annotations.py"):
                return fr.file_name, fr.start_line
    except Exception:
        pass
    return "<jaxpr>", 0


def _sub_jaxprs(params: dict):
    for val in params.values():
        for x in (val if isinstance(val, (tuple, list)) else (val,)):
            if isinstance(x, ClosedJaxpr):
                yield x.jaxpr
            elif isinstance(x, Jaxpr):
                yield x


def _build_prod(jaxpr: Jaxpr) -> dict:
    return {ov: eqn for eqn in jaxpr.eqns for ov in eqn.outvars}


def _dtype(v) -> np.dtype:
    return np.dtype(v.aval.dtype)


def _literal_value(v, prod, depth: int = 0) -> Optional[int]:
    """Integer value of a (possibly broadcast/converted) literal operand."""
    if isinstance(v, Literal):
        try:
            return int(np.max(np.asarray(v.val)))
        except Exception:
            return None
    e = prod.get(v)
    if e is not None and depth < 6 and e.primitive.name in (
            "broadcast_in_dim", "convert_element_type", "reshape"):
        return _literal_value(e.invars[0], prod, depth + 1)
    return None


# stack: [(producer_map, invar->caller-operand map)], innermost frame last —
# lets the backward walk fall through a sub-jaxpr's invars to the caller's
# operands (jnp.where traces as pjit[_where] wrapping the select_n)
_Stack = List[Tuple[dict, dict]]


def _origin(v, stack: _Stack, depth: int = 0) -> str:
    """Classify the producer of ``v``: 'bool' (from a boolean), 'digit'
    (⟨hi,lo⟩ base-2^16 extraction), 'select' (select/where-masked),
    'literal', 'opaque' (jaxpr input — nothing provable), or 'other'."""
    if depth > 24:
        return "other"
    if isinstance(v, Literal):
        return "literal"
    if _dtype(v) == np.bool_:
        return "bool"
    prod, invmap = stack[-1]
    e = prod.get(v)
    if e is None:
        if v in invmap and len(stack) > 1:
            return _origin(invmap[v], stack[:-1], depth + 1)
        return "opaque"
    p = e.primitive.name
    if p == "and":
        for o in e.invars:
            val = _literal_value(o, prod)
            if val is not None and val <= 0xFFFF:
                return "digit"
        return "other"
    if p == "shift_right_logical":
        val = _literal_value(e.invars[1], prod)
        return "digit" if val is not None and val >= 16 else "other"
    if p == "select_n":
        return "select"
    if p == "convert_element_type":
        if _dtype(e.invars[0]) == np.bool_:
            return "bool"
        return _origin(e.invars[0], stack, depth + 1)
    if p in _PASSTHRU:
        return _origin(e.invars[0], stack, depth + 1)
    if p in _CALL_PRIMS:
        subs = list(_sub_jaxprs(e.params))
        if len(subs) == 1:
            sub = subs[0]
            try:
                i = list(e.outvars).index(v)
            except ValueError:
                return "other"
            out = sub.outvars[i]
            if isinstance(out, Literal):
                return "literal"
            sinv = (dict(zip(sub.invars, e.invars))
                    if len(sub.invars) == len(e.invars) else {})
            return _origin(out, stack + [(_build_prod(sub), sinv)],
                           depth + 1)
        return "other"
    return "other"


@dataclasses.dataclass
class _Ctx:
    entry: str
    findings: List[Finding] = dataclasses.field(default_factory=list)
    # tag name -> [(file, line)] of its sites / set of tags flowing into it
    tag_sites: Dict[str, List[Tuple[str, int]]] = \
        dataclasses.field(default_factory=dict)
    tag_inputs: Dict[str, Set[str]] = dataclasses.field(default_factory=dict)

    def add(self, rule: str, file: str, line: int, msg: str) -> None:
        self.findings.append(Finding(
            rule=rule, level="jaxpr", file=file, line=line,
            msg=f"[{self.entry}] {msg}"))


def _check_eqn(eqn, prod, ctx: _Ctx) -> None:
    p = eqn.primitive.name
    stack: _Stack = [(prod, {})]
    if p in ("reduce_sum", "cumsum"):
        op = eqn.invars[0]
        if (_dtype(op) == TS_DTYPE
                and _origin(op, stack) not in ("bool", "digit", "literal")):
            f, ln = _frame(eqn)
            ctx.add("W02", f, ln,
                    f"uint32 `{p}` without uint64 widening or the exact "
                    "(hi, lo) base-2^16 digit split — wraps past 2^32 and "
                    "inverts timestamp dominance")
    elif p in ("reduce_min", "reduce_max"):
        op = eqn.invars[0]
        if (_dtype(op) == TS_DTYPE
                and _origin(op, stack) not in ("bool", "digit", "select",
                                               "literal")):
            f, ln = _frame(eqn)
            ctx.add("W02", f, ln,
                    f"uint32 `{p}` over an unmasked operand — a sentinel "
                    "or wrapped value hijacks the extremum")
    elif p in ("argmin", "argmax"):
        op = eqn.invars[0]
        if (_dtype(op) != np.bool_
                and _origin(op, stack) not in ("bool", "select")):
            f, ln = _frame(eqn)
            ctx.add("W03", f, ln,
                    f"`{p}` over a {_dtype(op)} operand that is not "
                    "select/where-masked — a -1/0xFFFFFFFF sentinel "
                    "hijacks the selection")


def _walk(jaxpr: Jaxpr, env: Dict, ctx: _Ctx) -> FrozenSet[str]:
    """Forward taint walk: env maps Var -> frozenset of tag names that flow
    into it. Returns the union of tags on the jaxpr's outputs. Unknown
    equations pass taint through (over-approximate, so A1's reachability
    check can only miss leaks, never invent them)."""
    prod = _build_prod(jaxpr)
    for eqn in jaxpr.eqns:
        in_tags: FrozenSet[str] = frozenset()
        for v in eqn.invars:
            if isinstance(v, Var):
                in_tags |= env.get(v, frozenset())
        out_tags = in_tags
        nm = str(eqn.params.get("name", ""))
        if eqn.primitive.name == "name" and nm.startswith(anno._NAMESPACE):
            t = nm[len(anno._NAMESPACE):]
            ctx.tag_sites.setdefault(t, []).append(_frame(eqn))
            ctx.tag_inputs.setdefault(t, set()).update(in_tags)
            out_tags = in_tags | {t}
        else:
            _check_eqn(eqn, prod, ctx)
        for sub in _sub_jaxprs(eqn.params):
            senv: Dict = {}
            if len(sub.invars) == len(eqn.invars):
                for sv, outer in zip(sub.invars, eqn.invars):
                    if isinstance(outer, Var):
                        senv[sv] = env.get(outer, frozenset())
            else:  # cond branches etc.: conservative — everything flows in
                for sv in sub.invars:
                    senv[sv] = in_tags
            out_tags |= _walk(sub, senv, ctx)
        for ov in eqn.outvars:
            env[ov] = out_tags
    ret: FrozenSet[str] = frozenset()
    for v in jaxpr.outvars:
        if isinstance(v, Var):
            ret |= env.get(v, frozenset())
    return ret


_REQUIRED_TAGS = (anno.LOCK_GRANTED, anno.LOCK_RELEASED,
                  anno.COMMIT_COMMITTED)


def _check_lock_pairing(ctx: _Ctx) -> None:
    """A1: grant mask must reach both the release tag and the commit tag."""
    missing = [t for t in _REQUIRED_TAGS if t not in ctx.tag_sites]
    if missing:
        site = ctx.tag_sites.get(anno.LOCK_GRANTED, [("<jaxpr>", 0)])[0]
        ctx.add("W01", site[0], site[1],
                f"protocol tags absent from the trace: {missing} — a "
                "CAS-acquire path lost its release/commit pairing (or its "
                "annotations.tag calls)")
        return
    for consumer in (anno.LOCK_RELEASED, anno.COMMIT_COMMITTED):
        if anno.LOCK_GRANTED not in ctx.tag_inputs.get(consumer, set()):
            f, ln = ctx.tag_sites[consumer][0]
            ctx.add("W01", f, ln,
                    f"the CAS grant mask does not flow into `{consumer}` — "
                    "locks leak on that outcome path")


def _load_text(file: str) -> Optional[str]:
    p = Path(file)
    try:
        return p.read_text() if p.is_file() else None
    except OSError:
        return None


# --------------------------------------------------------------------------
# entrypoint fixtures: tiny deterministic protocol states, traced only
# (make_jaxpr — nothing executes)
# --------------------------------------------------------------------------

def _fixture(n_threads: int = 6, n_records: int = 32, rs: int = 3,
             ws: int = 2, width: int = 4):
    oracle = VectorOracle(n_threads)
    table = mvcc.init_table(n_records, width)
    state = oracle.init()
    T = n_threads
    batch = TxnBatch(
        tid=jnp.arange(T, dtype=jnp.int32),
        read_slots=(jnp.arange(T * rs, dtype=jnp.int32).reshape(T, rs)
                    % n_records),
        read_mask=jnp.ones((T, rs), bool),
        write_ref=jnp.tile(jnp.arange(ws, dtype=jnp.int32), (T, 1)),
        write_mask=jnp.ones((T, ws), bool),
    )
    journal = wal.init_journal(T, capacity=4, n_slots=oracle.n_slots,
                               ws=ws, width=width)
    return oracle, table, state, batch, journal


def _trace_run_round() -> ClosedJaxpr:
    oracle, table, state, batch, journal = _fixture()
    ws = batch.write_ref.shape[1]

    def fn(tbl, vec, jnl):
        out = si.run_round(tbl, oracle, VectorState(vec=vec), batch,
                           lambda rh, rd, v: rd[:, :ws, :] + 1,
                           journal=jnl)
        return out.table, out.oracle_state, out.committed, out.journal

    return jax.make_jaxpr(fn)(table, state.vec, journal)


def _trace_distributed_round() -> ClosedJaxpr:
    # 5 threads over 2 shards: a non-dividing vector, so the pad_vector
    # path is part of the audited surface. Falls back to a 1-shard mesh on
    # a single device — the body jaxpr (tags, collectives, journal appends)
    # is identical in structure.
    n_shards = 2 if len(jax.devices()) >= 2 else 1
    oracle, table, state, batch, journal = _fixture(n_threads=5)
    n_records = table.cur_hdr.shape[0]
    mesh = compat.mesh(n_shards, "shard")
    round_fn, _ = store.distributed_round(
        mesh, "shard", oracle,
        lambda rh, rd, v, aux: rd[:, :batch.write_ref.shape[1], :] + 1,
        n_records // n_shards, shard_vector=True, with_journal=True)
    vec, _ = store.pad_vector(state.vec, n_shards)

    def fn(tbl, v, jnl):
        return round_fn(tbl, v, batch, None, journal=jnl)

    return jax.make_jaxpr(fn)(table, vec, journal)


def _trace_replay() -> ClosedJaxpr:
    _, table, state, batch, journal = _fixture()
    T, ws, width = batch.tid.shape[0], 2, 4
    # two real (eager) appends so `used` — which replay's ring-wrap check
    # reads on the host — is concrete and non-trivial
    j = journal
    for seq in range(2):
        # analysis: safe(W04): fixture builds exact journal-width arrays
        j = wal.append_intent(
            j, batch.tid, state.vec,
            jnp.zeros((T, ws), jnp.int32),
            jnp.zeros((T, ws, 2), jnp.uint32),
            jnp.zeros((T, ws, width), jnp.int32),
            jnp.ones((T, ws), bool), round_no=0, seq=seq)
        j = wal.append_outcome(j, batch.tid, jnp.ones((T,), bool))
    entry_fields = tuple(f for f in j._fields if f != "used")

    def fn(tbl, *vals):
        jj = j._replace(**dict(zip(entry_fields, vals)))
        return wal.replay(jj, tbl)

    return jax.make_jaxpr(fn)(
        table, *[getattr(j, f) for f in entry_fields])


def _trace_gc_round() -> ClosedJaxpr:
    oracle, table, state, _, _ = _fixture()
    log = gc_ops.init_log(4, oracle.n_slots)

    def fn(tbl, lg, vec):
        return gc_ops.gc_round(tbl, vec, lg, jnp.int32(100), jnp.int32(10))

    return jax.make_jaxpr(fn)(table, log, state.vec)


# name -> (tracer, expects_locks): expects_locks entrypoints contain a CAS
# acquire and must satisfy the full A1 pairing contract
ENTRYPOINTS: Dict[str, Tuple[Callable[[], ClosedJaxpr], bool]] = {
    "si.run_round": (_trace_run_round, True),
    "store.distributed_round": (_trace_distributed_round, True),
    "wal.replay": (_trace_replay, False),
    "gc.gc_round": (_trace_gc_round, False),
}


@dataclasses.dataclass
class EntrypointReport:
    name: str
    status: str       # "ok" | "error"
    detail: str = ""
    n_eqns: int = 0
    n_findings: int = 0   # active (unsuppressed) findings

    def to_json(self) -> dict:
        return dataclasses.asdict(self)


def _count_eqns(jaxpr: Jaxpr) -> int:
    n = len(jaxpr.eqns)
    for eqn in jaxpr.eqns:
        for sub in _sub_jaxprs(eqn.params):
            n += _count_eqns(sub)
    return n


def audit_jaxpr(closed: ClosedJaxpr, name: str,
                expects_locks: bool = False) -> List[Finding]:
    """Audit one already-traced closed jaxpr; suppressions applied."""
    ctx = _Ctx(entry=name)
    _walk(closed.jaxpr, {}, ctx)
    if expects_locks:
        _check_lock_pairing(ctx)
    apply_suppressions(ctx.findings, _load_text)
    return ctx.findings


def audit_callable(fn, *args, name: str = "callable",
                   expects_locks: bool = False) -> List[Finding]:
    """Trace ``fn(*args)`` and audit it — the corpus tests' entry hook. An
    [A4] width-guard trip during tracing becomes a W04 finding."""
    try:
        closed = jax.make_jaxpr(fn)(*args)
    except ValueError as e:
        if "[A4]" in str(e):
            return [Finding(rule="W04", level="jaxpr", file="<trace>",
                            line=0, msg=f"[{name}] {e}")]
        raise
    return audit_jaxpr(closed, name, expects_locks=expects_locks)


def audit_tree() -> Tuple[List[Finding], List[EntrypointReport]]:
    """Trace and audit every registered entrypoint. Findings are deduped by
    (rule, file, line) — shared helpers (mvcc, wal) appear in several
    traces."""
    findings: List[Finding] = []
    reports: List[EntrypointReport] = []
    seen: Set[Tuple[str, str, int]] = set()
    for name, (tracer, expects_locks) in ENTRYPOINTS.items():
        ctx = _Ctx(entry=name)
        try:
            closed = tracer()
        except ValueError as e:
            if "[A4]" in str(e):
                ctx.add("W04", "<trace>", 0, str(e))
                apply_suppressions(ctx.findings, _load_text)
                findings.extend(ctx.findings)
                reports.append(EntrypointReport(
                    name, "ok", detail="A4 width guard tripped",
                    n_findings=len(ctx.findings)))
                continue
            reports.append(EntrypointReport(
                name, "error", detail=f"{type(e).__name__}: {e}"))
            continue
        except Exception as e:  # an untraceable entrypoint is itself a bug
            reports.append(EntrypointReport(
                name, "error", detail=f"{type(e).__name__}: {e}"))
            continue
        _walk(closed.jaxpr, {}, ctx)
        if expects_locks:
            _check_lock_pairing(ctx)
        apply_suppressions(ctx.findings, _load_text)
        fresh = []
        for f in ctx.findings:
            key = (f.rule, f.file, f.line)
            if key not in seen:
                seen.add(key)
                fresh.append(f)
        findings.extend(fresh)
        reports.append(EntrypointReport(
            name, "ok", n_eqns=_count_eqns(closed.jaxpr),
            n_findings=sum(1 for f in fresh if not f.suppressed)))
    return findings, reports
