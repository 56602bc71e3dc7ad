"""Jaxpr-level determinism & contract audit of the compiled engines.

Where the AST lint sees source, this pass sees what XLA will actually
compile: each registered (balancer × backend) engine — plus one lane
per registered keep-alive policy — is traced via :func:`jax.make_jaxpr`
at a tiny shape (tracing only, no compilation) and the ClosedJaxpr is
walked for hazards that historically showed up as flaky parity
failures:

* ``JXP001`` — weak-typed engine outputs or scan/while carries (weak
  types re-promote at the next op and can diverge from the numpy
  oracle or recompile per call site),
* ``JXP002`` — carry pytree structure / dtype drift between scan
  iterations (jax itself errors on hard mismatches; the audit reports
  the aval diff readably and also covers while_loop carries),
* ``JXP003`` — 64-bit values in lanes declared 32-bit (the simulator
  engines are float64 *by design* and audit with ``allow_64=True``;
  kernel/toy lanes can pin 32-bit),
* ``JXP004`` — host callbacks (``debug_callback`` / ``pure_callback``
  / ``io_callback`` / infeed/outfeed) inside the compiled hot path,
* ``JXP005`` — engine-cache-key incompleteness: every
  ``ClusterCfg`` / ``LifecycleCfg`` / ``FleetCfg`` field is perturbed
  and the
  :func:`repro.core.simulator._cache_key` is probed — a field that
  changes the traced program but not the key would silently share a
  compiled engine between different configs,
* ``JXP006`` — 64-bit integer ``div``/``rem`` inside a scan or
  while body (the TPU emulates each as a long dependent chain, paid
  on every iteration).

:func:`audit_engines` also returns one stats row per engine (jaxpr eqn
count, scan count, carry leaves/bytes) — the raw material for the
budget gate in :mod:`repro.analysis.budgets`.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Iterable, Optional

import numpy as np

from .findings import Finding
from .rules import RULES

#: Primitive names that run code on host mid-program.
HOST_CALLBACK_PRIMS = frozenset({
    "debug_callback", "debug_print", "pure_callback", "io_callback",
    "callback", "infeed", "outfeed", "host_callback_call",
})

#: Tiny audit shape — tracing cost only, results never executed.
AUDIT_N, AUDIT_F, AUDIT_W = 8, 3, 3


def _jax():
    import jax  # deferred so `--no-jaxpr` lint runs never import jax
    return jax


# --------------------------------------------------------------------------
# jaxpr walking helpers
# --------------------------------------------------------------------------

def _sub_jaxprs(params: dict):
    from jax.extend.core import ClosedJaxpr, Jaxpr
    for v in params.values():
        vals = v if isinstance(v, (list, tuple)) else (v,)
        for item in vals:
            if isinstance(item, ClosedJaxpr):
                yield item.jaxpr
            elif isinstance(item, Jaxpr):
                yield item
            elif hasattr(item, "jaxpr") and hasattr(item, "consts"):
                yield item.jaxpr


def iter_eqns(jaxpr) -> Iterable:
    """All eqns of ``jaxpr`` and (recursively) its sub-jaxprs."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)  # unwrap ClosedJaxpr
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn.params):
            yield from iter_eqns(sub)


def iter_loop_eqns(jaxpr, inside: bool = False) -> Iterable:
    """Eqns of ``jaxpr`` that run inside a scan or while body, each once."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    for eqn in jaxpr.eqns:
        if inside:
            yield eqn
        loop = inside or eqn.primitive.name in ("scan", "while")
        for sub in _sub_jaxprs(eqn.params):
            yield from iter_loop_eqns(sub, loop)


def count_eqns(jaxpr) -> int:
    return sum(1 for _ in iter_eqns(jaxpr))


def _aval_str(aval) -> str:
    weak = ", weak" if getattr(aval, "weak_type", False) else ""
    return f"{getattr(aval, 'dtype', '?')}{getattr(aval, 'shape', '?')}" \
           f"{weak}"


def _avals_mismatch(a, b) -> bool:
    return (getattr(a, "shape", None) != getattr(b, "shape", None)
            or getattr(a, "dtype", None) != getattr(b, "dtype", None)
            or getattr(a, "weak_type", False)
            != getattr(b, "weak_type", False))


# --------------------------------------------------------------------------
# single-program audit (also the unit-testable entry point)
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class JaxprStats:
    label: str
    eqns: int
    scans: int
    whiles: int
    carry_leaves: int
    carry_bytes: int
    outputs: int

    def row(self) -> dict:
        return dataclasses.asdict(self)


def audit_jaxpr(closed, *, label: str = "<fn>",
                allow_64: bool = True,
                allow_weak_outputs: bool = False
                ) -> tuple[JaxprStats, list[Finding]]:
    """Walk one ClosedJaxpr; returns (stats, findings)."""
    findings: list[Finding] = []
    loc = f"<jaxpr:{label}>"

    def find(rule: str, msg: str):
        findings.append(Finding(path=loc, line=0, rule=rule,
                                message=msg, hint=RULES[rule].hint))

    scans = whiles = 0
    carry_leaves = 0
    carry_bytes = 0
    for eqn in iter_eqns(closed):
        prim = eqn.primitive.name
        if prim in HOST_CALLBACK_PRIMS or prim.endswith("_callback"):
            find("JXP004", f"host callback primitive '{prim}' in "
                           f"compiled program")
        if prim == "scan":
            scans += 1
            body = eqn.params["jaxpr"]
            nc = eqn.params["num_carry"]
            nconsts = eqn.params["num_consts"]
            cin = list(body.in_avals)[nconsts:nconsts + nc]
            cout = list(body.out_avals)[:nc]
            for i, (a, b) in enumerate(zip(cin, cout)):
                if _avals_mismatch(a, b):
                    find("JXP002",
                         f"scan carry leaf {i} drifts across "
                         f"iterations: {_aval_str(a)} -> {_aval_str(b)}")
            carry_leaves += nc
            for a in cin:
                carry_bytes += int(np.prod(a.shape, dtype=np.int64)
                                   * a.dtype.itemsize)
                if getattr(a, "weak_type", False):
                    find("JXP001", f"weak-typed scan carry leaf "
                                   f"{_aval_str(a)}")
        elif prim == "while":
            whiles += 1
            body = eqn.params["body_jaxpr"]
            nconsts = eqn.params["body_nconsts"]
            cin = list(body.in_avals)[nconsts:]
            cout = list(body.out_avals)
            for i, (a, b) in enumerate(zip(cin, cout)):
                if _avals_mismatch(a, b):
                    find("JXP002",
                         f"while carry leaf {i} drifts across "
                         f"iterations: {_aval_str(a)} -> {_aval_str(b)}")
                if getattr(a, "weak_type", False):
                    find("JXP001", f"weak-typed while carry leaf "
                                   f"{_aval_str(a)}")

    div64 = [eqn.primitive.name for eqn in iter_loop_eqns(closed)
             if eqn.primitive.name in ("div", "rem")
             and eqn.outvars[0].aval.dtype.kind in ("i", "u")
             and eqn.outvars[0].aval.dtype.itemsize == 8]
    if div64:
        find("JXP006", f"{len(div64)} 64-bit integer div/rem inside "
                       f"scan/while bodies "
                       f"({', '.join(sorted(set(div64)))})")

    out_avals = closed.out_avals
    for i, a in enumerate(out_avals):
        if getattr(a, "weak_type", False) and not allow_weak_outputs:
            find("JXP001", f"weak-typed program output {i}: "
                           f"{_aval_str(a)}")
        if not allow_64 and getattr(a, "dtype", None) is not None \
                and a.dtype.itemsize == 8 \
                and a.dtype.kind in ("f", "i", "u", "c"):
            find("JXP003", f"64-bit output {i} ({_aval_str(a)}) in a "
                           f"lane declared 32-bit")
    if not allow_64:
        for eqn in iter_eqns(closed):
            for v in eqn.outvars:
                a = getattr(v, "aval", None)
                dt = getattr(a, "dtype", None)
                if dt is not None and dt.itemsize == 8 \
                        and dt.kind in ("f", "i", "u", "c"):
                    find("JXP003",
                         f"64-bit intermediate from primitive "
                         f"'{eqn.primitive.name}' ({_aval_str(a)})")
                    break
            else:
                continue
            break

    stats = JaxprStats(label=label, eqns=count_eqns(closed), scans=scans,
                       whiles=whiles, carry_leaves=carry_leaves,
                       carry_bytes=carry_bytes, outputs=len(out_avals))
    return stats, findings


def audit_fn(fn: Callable, *example_args, label: str = "<fn>",
             allow_64: bool = True, allow_weak_outputs: bool = False
             ) -> tuple[JaxprStats, list[Finding]]:
    """Trace ``fn`` on example args/ShapeDtypeStructs and audit it."""
    jax = _jax()
    closed = jax.make_jaxpr(fn)(*example_args)
    return audit_jaxpr(closed, label=label, allow_64=allow_64,
                       allow_weak_outputs=allow_weak_outputs)


# --------------------------------------------------------------------------
# engine enumeration + tracing
# --------------------------------------------------------------------------

def _audit_cluster(lifecycle=None, fleet=None):
    from repro.core.cluster import ClusterCfg
    return ClusterCfg(n_workers=AUDIT_W, cores=2, capacity_factor=2,
                      lifecycle=lifecycle, fleet=fleet)


def iter_engine_specs(*, balancers: Optional[Iterable[str]] = None,
                      sched: str = "PS") -> list[tuple]:
    """(label, policy, cluster, backend, telemetry, chunk, timeline)
    per engine.

    Covers every (balancer × traceable backend) pair in the registry —
    backends are ``jax`` plus ``pallas`` (balancers without a kernel
    run their jax implementation under the pallas backend, exactly as
    :func:`repro.policy.registry._pallas_select` dispatches them) —
    plus one ``jax`` lane per registered keep-alive policy (balancer
    ``LL``) so lifecycle carries are audited too, plus ``|tel`` lanes
    (telemetry-on variants of representative engines: stateless,
    kernel, carried-state, lifecycle and late binding) so the streaming
    telemetry carry is covered by the jaxpr rules and eqn budgets,
    plus ``|fleet`` lanes (heterogeneous two-gen speeds under the
    speed-blind LL and the speed-learning SWARM balancers, and one
    ``|fleet|auto|tel`` lane with the ``TARGET_P99`` autoscaler carry
    riding the telemetry sketch), plus ``|chunk`` lanes (the streaming
    chunk engine's per-segment scan — same arrival/completion bodies
    with the slot mirrors and exact-counter carry; ``chunk`` is
    ``None`` for monolithic lanes), plus ``|tl`` lanes (the windowed
    flight-recorder plane of :mod:`repro.telemetry.timeline` riding
    the carry — alone, stacked on telemetry, on the hybrid balancer
    whose mode flips it logs, under the autoscaler whose decisions it
    logs, and through the chunk engine; ``timeline`` is the trailing
    tuple element, ``None`` when the plane is off).
    """
    from repro.core.taxonomy import Binding, PolicySpec
    from repro.fleet import FleetCfg
    from repro.lifecycle import LifecycleCfg
    from repro.lifecycle.registry import keepalive_names
    from repro.policy import balancer_names
    from repro.telemetry import TelemetryCfg
    names = tuple(balancers) if balancers is not None \
        else balancer_names()
    specs: list[tuple] = []
    plain = _audit_cluster()
    for bname in names:
        pol = PolicySpec(Binding.EARLY, bname, sched)
        for backend in ("jax", "pallas"):
            specs.append((f"{pol.name}|{backend}", pol, plain, backend,
                          None))
    if balancers is None:
        pol = PolicySpec(Binding.EARLY, "LL", sched)
        for ka in keepalive_names():
            cl = _audit_cluster(LifecycleCfg(keepalive=ka))
            specs.append((f"{pol.name}|jax|ka={ka}", pol, cl, "jax",
                          None))
        # the late-binding engine (no balancer axis, controller queue)
        late = PolicySpec(Binding.LATE, "LL", "FCFS")
        specs.append((f"{late.name}|jax", late, plain, "jax", None))
        # telemetry-on lanes — one per engine family, not the full
        # product (the telemetry carry is policy-independent)
        tel = TelemetryCfg()
        for bname in ("LL", "H", "HIKU"):
            p = PolicySpec(Binding.EARLY, bname, sched)
            specs.append((f"{p.name}|jax|tel", p, plain, "jax", tel))
        ph = PolicySpec(Binding.EARLY, "H", sched)
        specs.append((f"{ph.name}|pallas|tel", ph, plain, "pallas", tel))
        cl = _audit_cluster(LifecycleCfg(keepalive="FIXED_TTL"))
        specs.append((f"{pol.name}|jax|ka=FIXED_TTL|tel", pol, cl,
                      "jax", tel))
        specs.append((f"{late.name}|jax|tel", late, plain, "jax", tel))
        # heterogeneous-fleet lanes: speed vectors thread the scan for
        # a speed-blind and a speed-learning balancer, and one
        # autoscaler lane carries the MIAD controller state (needs the
        # telemetry sketch it reads)
        het = _audit_cluster(fleet=FleetCfg(preset="two-gen"))
        for bname in ("LL", "SWARM"):
            p = PolicySpec(Binding.EARLY, bname, sched)
            specs.append((f"{p.name}|jax|fleet", p, het, "jax", None))
        auto = _audit_cluster(fleet=FleetCfg(
            preset="two-gen", autoscale="TARGET_P99", target_p99=4.0,
            min_workers=1, cooldown_s=1.0))
        specs.append((f"{pol.name}|jax|fleet|auto|tel", pol, auto,
                      "jax", tel))
        # streaming chunk-engine lanes: plain, telemetry-on, the
        # heaviest lifecycle carry, and the full autoscaler stack —
        # budgeted under their own ``|chunk`` labels (the chunk scan
        # adds slot mirrors + exact counters to the carry)
        kacl = _audit_cluster(LifecycleCfg(keepalive="HYBRID_HIST"))
        for lane, cl2, t2 in ((f"{pol.name}|jax|chunk", plain, None),
                              (f"{pol.name}|jax|tel|chunk", plain, tel),
                              (f"{pol.name}|jax|ka=HYBRID_HIST|tel"
                               f"|chunk", kacl, tel),
                              (f"{pol.name}|jax|fleet|auto|tel|chunk",
                               auto, tel)):
            specs.append((lane, pol, cl2, "jax", t2, AUDIT_N))
        # windowed-timeline lanes: the flight-recorder plane alone,
        # stacked on the telemetry sketch, on the hybrid balancer
        # (whose mode flips it logs), under the autoscaler (whose
        # grow/shrink decisions it logs), and riding the chunk
        # engine's carry across segment boundaries
        from repro.telemetry import TimelineCfg
        tl = TimelineCfg()
        for lane, p3, cl3, t3, ch3 in (
                (f"{pol.name}|jax|tl", pol, plain, None, None),
                (f"{pol.name}|jax|tel|tl", pol, plain, tel, None),
                (f"{ph.name}|jax|tel|tl", ph, plain, tel, None),
                (f"{pol.name}|jax|fleet|auto|tel|tl", pol, auto, tel,
                 None),
                (f"{pol.name}|jax|tel|tl|chunk", pol, plain, tel,
                 AUDIT_N)):
            specs.append((lane, p3, cl3, "jax", t3, ch3, tl))
    return [s + (None,) * (7 - len(s)) for s in specs]


def trace_engine(policy, cluster, backend: str = "jax",
                 n_arrivals: int = AUDIT_N, n_functions: int = AUDIT_F,
                 telemetry=None, timeline=None):
    """``jax.make_jaxpr`` of the raw scan engine (tracing only)."""
    jax = _jax()
    import jax.numpy as jnp
    from repro.core.simulator import _build_engine
    run = _build_engine(policy, cluster, n_arrivals, n_functions,
                        backend, telemetry=telemetry,
                        timeline=timeline)
    N, F = n_arrivals, n_functions
    f64 = jax.ShapeDtypeStruct((N,), jnp.float64)
    # funcs and homes are int32, as every entry point passes them
    funcs = jax.ShapeDtypeStruct((N,), jnp.int32)
    homes = jax.ShapeDtypeStruct((F,), jnp.int32)
    return jax.make_jaxpr(run)(f64, funcs, f64, f64, homes)


def trace_stream_engine(policy, cluster, backend: str = "jax",
                        chunk: int = AUDIT_N,
                        n_functions: int = AUDIT_F, telemetry=None,
                        timeline=None):
    """``jax.make_jaxpr`` of the streaming chunk scan (one segment).

    The carry avals come from the engine's own ``init`` (leading rep
    axis stripped), so the traced program is exactly what one
    ``step_fn`` dispatch of :func:`repro.core.streaming
    .simulate_stream` compiles per replication.
    """
    jax = _jax()
    import jax.numpy as jnp
    from repro.core.simulator import _build_engine
    init, run_chunk, _ = _build_engine(
        policy, cluster, int(chunk), n_functions, backend,
        telemetry=telemetry, timeline=timeline, stream=True)
    st = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype), init(1, 0))
    k, F = int(chunk), n_functions
    f64 = jax.ShapeDtypeStruct((k,), jnp.float64)
    gids = jax.ShapeDtypeStruct((k,), jnp.int64)
    valid = jax.ShapeDtypeStruct((k,), jnp.bool_)
    funcs = jax.ShapeDtypeStruct((k,), jnp.int32)
    homes = jax.ShapeDtypeStruct((F,), jnp.int32)
    return jax.make_jaxpr(run_chunk)(st, gids, valid, f64, funcs, f64,
                                     f64, homes)


def audit_engines(*, balancers: Optional[Iterable[str]] = None
                  ) -> tuple[list[JaxprStats], list[Finding]]:
    """Trace + audit every engine spec; returns (stats, findings)."""
    all_stats: list[JaxprStats] = []
    findings: list[Finding] = []
    for label, policy, cluster, backend, telemetry, chunk, timeline \
            in iter_engine_specs(balancers=balancers):
        if chunk is not None:
            closed = trace_stream_engine(policy, cluster, backend,
                                         chunk=chunk,
                                         telemetry=telemetry,
                                         timeline=timeline)
        else:
            closed = trace_engine(policy, cluster, backend,
                                  telemetry=telemetry,
                                  timeline=timeline)
        stats, fs = audit_jaxpr(closed, label=label, allow_64=True)
        all_stats.append(stats)
        findings.extend(fs)
    return all_stats, findings


# --------------------------------------------------------------------------
# engine-cache-key completeness probe (JXP005)
# --------------------------------------------------------------------------

def _perturb(value: Any, field: str):
    """A different-but-valid value for a config field, or None to skip."""
    if field == "keepalive":
        from repro.lifecycle.registry import keepalive_names
        others = [k for k in keepalive_names() if k != value]
        return others[0] if others else None
    if field == "coldstart":
        return "paper-sim" if value != "paper-sim" else "scalar"
    if field == "preset":
        return "two-gen" if value != "two-gen" else "long-tail"
    if field == "autoscale":
        return "TARGET_P99" if value != "TARGET_P99" else "STATIC"
    if field in ("speed", "mem"):
        return (1.0,) * AUDIT_W if value == () else ()
    if isinstance(value, bool):
        return not value
    if isinstance(value, int):
        return value + 1
    if isinstance(value, float):
        return value + 1.0
    if isinstance(value, str):
        return value + "_x"
    return None


def audit_cache_key() -> list[Finding]:
    """Probe ``build_simulator``'s memo key against every config field.

    For each ``ClusterCfg`` field (and each ``LifecycleCfg`` /
    ``FleetCfg`` sub-field) a perturbed config is built; if the
    engine-cache key does not
    change, two different configs would share one compiled engine —
    the bug class the PR-6 satellite regression test locks in.
    """
    from repro.core.simulator import _cache_key
    from repro.core.taxonomy import parse_policy
    from repro.fleet import FleetCfg
    from repro.lifecycle import LifecycleCfg
    findings: list[Finding] = []
    policy = parse_policy("E/LL/PS")

    def probe(base, changed, field: str):
        k0 = _cache_key(policy, base, AUDIT_N, AUDIT_F, False, "jax")
        k1 = _cache_key(policy, changed, AUDIT_N, AUDIT_F, False, "jax")
        if k0 == k1:
            findings.append(Finding(
                path=f"<cache-key:{field}>", line=0, rule="JXP005",
                message=f"configs differing in '{field}' share an "
                        f"engine cache key", hint=RULES["JXP005"].hint))

    base = _audit_cluster()
    for field in type(base)._fields:
        value = getattr(base, field)
        if field == "lifecycle":
            probe(base, base._replace(lifecycle=LifecycleCfg()),
                  "lifecycle")
            continue
        if field == "fleet":
            probe(base, base._replace(fleet=FleetCfg()), "fleet")
            continue
        new = _perturb(value, field)
        if new is None:
            continue
        probe(base, base._replace(**{field: new}), field)

    fbase = _audit_cluster(fleet=FleetCfg())
    for field in FleetCfg._fields:
        value = getattr(fbase.fleet, field)
        new = _perturb(value, field)
        if new is None:
            continue
        probe(fbase, fbase._replace(
            fleet=fbase.fleet._replace(**{field: new})),
            f"fleet.{field}")

    lbase = _audit_cluster(LifecycleCfg())
    for field in LifecycleCfg._fields:
        value = getattr(lbase.lifecycle, field)
        new = _perturb(value, field)
        if new is None:
            continue
        probe(lbase, lbase._replace(
            lifecycle=lbase.lifecycle._replace(**{field: new})),
            f"lifecycle.{field}")

    # telemetry is part of the traced program (python-gated carry), so
    # it must be part of the key: off vs on, and every TelemetryCfg
    # field perturbed
    from repro.telemetry import TelemetryCfg

    def probe_tel(t0, t1, field: str):
        k0 = _cache_key(policy, base, AUDIT_N, AUDIT_F, False, "jax", t0)
        k1 = _cache_key(policy, base, AUDIT_N, AUDIT_F, False, "jax", t1)
        if k0 == k1:
            findings.append(Finding(
                path=f"<cache-key:{field}>", line=0, rule="JXP005",
                message=f"configs differing in '{field}' share an "
                        f"engine cache key", hint=RULES["JXP005"].hint))

    tbase = TelemetryCfg()
    probe_tel(None, tbase, "telemetry")
    for field in TelemetryCfg._fields:
        new = _perturb(getattr(tbase, field), field)
        if new is None:
            continue
        probe_tel(tbase, tbase._replace(**{field: new}),
                  f"telemetry.{field}")

    # the chunk size is its own key component: a monolithic engine and
    # a chunked one (and two different chunk sizes) must never share a
    # compiled program
    def probe_chunk(c0, c1, field: str):
        k0 = _cache_key(policy, base, AUDIT_N, AUDIT_F, True, "jax",
                        None, c0)
        k1 = _cache_key(policy, base, AUDIT_N, AUDIT_F, True, "jax",
                        None, c1)
        if k0 == k1:
            findings.append(Finding(
                path=f"<cache-key:{field}>", line=0, rule="JXP005",
                message=f"configs differing in '{field}' share an "
                        f"engine cache key", hint=RULES["JXP005"].hint))

    probe_chunk(None, AUDIT_N, "chunk")
    probe_chunk(AUDIT_N, 2 * AUDIT_N, "chunk.size")

    # the timeline plane is python-gated into the carry exactly like
    # telemetry, so it is the key's trailing component: off vs on, and
    # every TimelineCfg field perturbed (n_windows/coarse_bins resize
    # carry planes; max_events resizes the event log; window_s is
    # baked into the traced window-index arithmetic)
    from repro.telemetry import TimelineCfg

    def probe_timeline(t0, t1, field: str):
        k0 = _cache_key(policy, base, AUDIT_N, AUDIT_F, False, "jax",
                        None, None, t0)
        k1 = _cache_key(policy, base, AUDIT_N, AUDIT_F, False, "jax",
                        None, None, t1)
        if k0 == k1:
            findings.append(Finding(
                path=f"<cache-key:{field}>", line=0, rule="JXP005",
                message=f"configs differing in '{field}' share an "
                        f"engine cache key", hint=RULES["JXP005"].hint))

    wbase = TimelineCfg()
    probe_timeline(None, wbase, "timeline")
    for field in TimelineCfg._fields:
        new = _perturb(getattr(wbase, field), field)
        if new is None:
            continue
        probe_timeline(wbase, wbase._replace(**{field: new}),
                       f"timeline.{field}")
    return findings


def run_audit(*, balancers: Optional[Iterable[str]] = None
              ) -> tuple[list[JaxprStats], list[Finding]]:
    """Full jaxpr pass: engine audits + cache-key probe."""
    stats, findings = audit_engines(balancers=balancers)
    findings.extend(audit_cache_key())
    return stats, findings
