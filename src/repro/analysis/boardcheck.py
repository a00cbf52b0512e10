"""Static board certification: trace a DUT engine to a closed jaxpr via
abstract eval ONLY — no device dispatch, no compile — and walk its
equations for the hazard classes behind every farm bug to date.

The engine contract under test is the farm's:

    engine(state, shell, batch_stack) -> (state', shell_snapshot, ys)

Certification abstractifies the job's initial trees to
``ShapedArray``\\ s (so even closed-over constants are never fetched or
copied) and runs ``jax.make_jaxpr`` — tracing is pure Python
interpretation of the engine body; nothing touches a device. The one
optional lowering (:func:`_donated_argnums`, to read the jit wrapper's
donation metadata) stops at StableHLO, before any backend compile.
:func:`no_dispatch_guard` makes that property checkable in tests: it
fails the process on any backend compile while certification runs.

Rule catalog (``RULES``) — each rule encodes a bug this repo actually
shipped and then fixed, so severity = "would the farm have eaten it":

=======  ========  ===========================================================
rule     severity  hazard
=======  ========  ===========================================================
ZC100    error     engine is not abstractly traceable (certification cannot
                   see inside it; closure-host engines must opt out of
                   certification, not slip through)
ZC101    error     host callback (``pure_callback``/``io_callback``/
                   ``debug_callback``) inside the window body — a hidden
                   host sync per window (the PR 5 eager ``_arg_signature``
                   stall class) and a nondeterminism hole under replay
ZC102    error     donation of an argnum other than state arg 0 — a donated
                   shell/stack invalidates the drain snapshot the scheduler
                   hands back
ZC103    error     donating engine paired with a NON-factory initial state —
                   the PR 5 "Array has been deleted" replay-crash class:
                   requeue would re-dispatch from a donated-and-deleted tree
ZC104    error     carry-out treedef/shape/dtype mismatch vs carry-in — the
                   scheduler feeds window *k*'s carry into window *k+1*, so
                   a drifting carry silently retraces EVERY window
ZC105    warning   carry weak-type drift (same silent-retrace mechanism, but
                   stabilizes after one retrace)
ZC106    warning   a PRNG key consumed by multiple sampling primitives
                   without an intervening split/fold — correlated streams,
                   and correlated LANES once the board is vmap-coalesced
ZC107    error     ``ScopeSpec(fuse=True)`` plane over a donating engine —
                   the fused counter update reads DUT leaves the dispatch
                   just donated
=======  ========  ===========================================================
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Callable, List, Optional

import jax
import jax.tree_util as tu
from jax.core import ShapedArray
from jax.extend.core import ClosedJaxpr, Jaxpr, Literal, Var

#: rule id -> (severity, one-line catalog entry)
RULES = {
    "ZC100": ("error", "engine not abstractly traceable"),
    "ZC101": ("error", "host callback inside the window body"),
    "ZC102": ("error", "donation of a non-state argnum"),
    "ZC103": ("error", "donating engine with non-factory initial state"),
    "ZC104": ("error", "carry-out structure/shape/dtype mismatch"),
    "ZC105": ("warning", "carry weak-type drift (retrace)"),
    "ZC106": ("warning", "PRNG key reused by multiple samplers"),
    "ZC107": ("error", "fused scope plane over a donating engine"),
}

_CALLBACK_PRIMS = frozenset(
    {"pure_callback", "io_callback", "debug_callback"})
# sampling primitives CONSUME a key (two consumptions of one key =
# identical streams); split/fold DERIVE fresh keys and act as barriers;
# wrap/unwrap are aliases between raw uint32 and typed key forms.
_SAMPLING_PRIMS = frozenset(
    {"random_bits", "threefry2x32", "random_gamma"})
_DERIVE_PRIMS = frozenset(
    {"random_split", "random_fold_in", "random_clone", "random_seed"})
_ALIAS_PRIMS = frozenset({"random_wrap", "random_unwrap"})


@dataclasses.dataclass(frozen=True)
class Finding:
    """One certification finding: a rule hit with its evidence."""
    rule: str
    severity: str
    summary: str
    detail: str = ""

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)

    def __str__(self):
        return f"{self.rule} [{self.severity}] {self.summary}"


@dataclasses.dataclass
class CertReport:
    """The certification verdict for one board."""
    name: str
    findings: List[Finding] = dataclasses.field(default_factory=list)

    @property
    def errors(self) -> List[Finding]:
        return [f for f in self.findings if f.severity == "error"]

    @property
    def warnings(self) -> List[Finding]:
        return [f for f in self.findings if f.severity == "warning"]

    @property
    def ok(self) -> bool:
        return not self.errors

    def summary(self) -> str:
        if not self.findings:
            return f"{self.name}: certified clean"
        parts = ", ".join(str(f) for f in self.findings)
        verdict = "CERTIFY FAIL" if self.errors else "certified with warnings"
        return f"{self.name}: {verdict} — {parts}"


def _finding(rule: str, summary: str, detail: str = "") -> Finding:
    severity, _ = RULES[rule]
    return Finding(rule=rule, severity=severity, summary=summary,
                   detail=detail)


# --------------------------------------------------------------- avals --
def _abstractify(tree):
    """Concrete pytree -> ShapedArray pytree (weak types preserved).
    Certification only ever traces over these, so a closed-over device
    array is never copied, fetched, or donated by the certifier."""
    from jax.api_util import shaped_abstractify

    def leaf(x):
        if isinstance(x, (ShapedArray, jax.ShapeDtypeStruct)):
            a = x
        else:
            a = shaped_abstractify(x)
        return ShapedArray(a.shape, a.dtype,
                           weak_type=getattr(a, "weak_type", False))
    return jax.tree.map(leaf, tree)


def _leaf_name(treedef, index: int) -> str:
    """Best-effort leaf path label for ``index`` in flatten order."""
    try:
        paths = [tu.keystr(p) for p, _ in
                 tu.tree_flatten_with_path(tu.tree_unflatten(
                     treedef, list(range(treedef.num_leaves))))[0]]
        return paths[index] or f"leaf[{index}]"
    except Exception:   # noqa: BLE001 — label only
        return f"leaf[{index}]"


# ------------------------------------------------------------ donation --
def _donated_argnums(engine: Callable, avals) -> tuple:
    """Positional argnums ``engine`` donates, read from the jit wrapper's
    lowering metadata (``Lowered.args_info``). A plain Python engine (no
    ``.lower``) donates nothing by construction. Lowering stops at
    StableHLO — no backend compile, no dispatch."""
    if not hasattr(engine, "lower"):
        return ()
    try:
        info = engine.lower(*avals).args_info[0]
    except Exception:   # noqa: BLE001 — unlowerable: tracing rules
        return ()       # (ZC100) already cover it
    donated = []
    for i, sub in enumerate(info):
        leaves = tu.tree_leaves(
            sub, is_leaf=lambda x: hasattr(x, "donated"))
        if any(getattr(leaf, "donated", False) for leaf in leaves):
            donated.append(i)
    return tuple(donated)


# ------------------------------------------------------- jaxpr walking --
def _sub_jaxprs(eqn):
    """Every (closed) sub-jaxpr hanging off ``eqn``'s params."""
    out = []
    for v in eqn.params.values():
        vs = v if isinstance(v, (tuple, list)) else (v,)
        for u in vs:
            if isinstance(u, ClosedJaxpr):
                out.append(u.jaxpr)
            elif isinstance(u, Jaxpr):
                out.append(u)
    return out


def _walk_eqns(jaxpr: Jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn):
            yield from _walk_eqns(sub)


def _find_callbacks(jaxpr: Jaxpr) -> List[Finding]:
    found = []
    for eqn in _walk_eqns(jaxpr):
        if eqn.primitive.name in _CALLBACK_PRIMS:
            cb = eqn.params.get("callback")
            label = getattr(cb, "__name__", None) or repr(cb)
            found.append(_finding(
                "ZC101",
                f"{eqn.primitive.name} in window body",
                f"callback={label}: every window dispatch round-trips "
                f"through the host — a hidden sync point (and a replay "
                f"nondeterminism hole: callbacks re-fire on requeue)"))
    return found


def _is_keyish(v) -> bool:
    if isinstance(v, Literal):
        return False
    aval = getattr(v, "aval", None)
    if aval is None or not hasattr(aval, "dtype"):
        return False
    try:
        if jax.dtypes.issubdtype(aval.dtype, jax.dtypes.prng_key):
            return True
    except Exception:   # noqa: BLE001 — exotic dtype: not a key
        return False
    import numpy as np
    return aval.dtype == np.uint32


def _key_sample_counts(jaxpr: Jaxpr, counts=None, alias=None):
    """Per-var count of SAMPLING consumptions in (and below) this scope,
    with wrap/unwrap aliased back to their source var and derive
    primitives (split/fold_in) acting as barriers. Returns the dict for
    this scope's vars; callers map invar positions back up."""
    counts = {} if counts is None else counts
    alias = {} if alias is None else alias

    def root(v):
        while v in alias:
            v = alias[v]
        return v

    def bump(v, n=1):
        if isinstance(v, Var):
            r = root(v)
            counts[r] = counts.get(r, 0) + n

    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in _ALIAS_PRIMS:
            if eqn.invars and isinstance(eqn.invars[0], Var):
                for ov in eqn.outvars:
                    alias[ov] = eqn.invars[0]
            continue
        if name in _DERIVE_PRIMS:
            continue            # consumes, but derives fresh streams
        if name in _SAMPLING_PRIMS:
            for v in eqn.invars:
                if _is_keyish(v):
                    bump(v)
            continue
        subs = _sub_jaxprs(eqn)
        if not subs:
            continue
        if name == "scan":
            body = eqn.params["jaxpr"].jaxpr
            n_consts = eqn.params.get("num_consts", 0)
            sub_counts = _key_sample_counts(body)
            for pos, iv in enumerate(body.invars):
                c = sub_counts.get(iv, 0)
                if c and pos < len(eqn.invars):
                    # a key entering as a scan CONST is re-consumed every
                    # iteration: one textual use is many runtime uses
                    bump(eqn.invars[pos], 2 * c if pos < n_consts else c)
            continue
        for sub in subs:
            sub_counts = _key_sample_counts(sub)
            if len(sub.invars) == len(eqn.invars):
                for pos, iv in enumerate(sub.invars):
                    c = sub_counts.get(iv, 0)
                    if c:
                        bump(eqn.invars[pos], c)
            else:
                # conservative: positions don't line up (cond branches,
                # while cond/body splits) — surface reuse found INSIDE
                for iv, c in sub_counts.items():
                    if c >= 2 and _is_keyish(iv):
                        counts[iv] = c
    return counts


def _find_key_reuse(closed: ClosedJaxpr, in_treedef,
                    n_state: int) -> List[Finding]:
    counts = _key_sample_counts(closed.jaxpr)
    reused = sorted(
        (v for v, c in counts.items()
         if c >= 2 and isinstance(v, Var) and _is_keyish(v)),
        key=lambda v: counts[v], reverse=True)
    findings = []
    invars = list(closed.jaxpr.invars)
    for v in reused:
        where = ""
        if v in invars:
            idx = invars.index(v)
            section = "state" if idx < n_state else "shell/stack"
            where = (f" (input {_leaf_name(in_treedef, idx)}"
                     f" in the {section} tree)")
        findings.append(_finding(
            "ZC106",
            f"PRNG key sampled {counts[v]}x without a split{where}",
            "identical random streams per consumption — and identical "
            "streams across LANES once this board is vmap-coalesced; "
            "derive per-use keys with jax.random.split/fold_in"))
        break   # one finding per engine: the fix (split discipline) is
        # global, and one rule-triggering fixture maps to one finding
    return findings


# ------------------------------------------------------ carry contract --
def _compare_carry(label: str, in_avals, in_treedef, out_struct,
                   out_avals) -> List[Finding]:
    findings = []
    out_treedef = tu.tree_structure(out_struct)
    if out_treedef != in_treedef:
        findings.append(_finding(
            "ZC104",
            f"{label} carry treedef changed across the window",
            f"in {in_treedef}, out {out_treedef}: the scheduler feeds "
            f"window k's carry into window k+1 — every window retraces"))
        return findings
    for i, (ia, oa) in enumerate(zip(in_avals, out_avals)):
        leaf = _leaf_name(in_treedef, i)
        if ia.shape != oa.shape or ia.dtype != oa.dtype:
            findings.append(_finding(
                "ZC104",
                f"{label} carry leaf {leaf} drifts "
                f"{ia.str_short()} -> {oa.str_short()}",
                "shape/dtype drift in the window carry retraces the "
                "engine on every window dispatch"))
        elif getattr(ia, "weak_type", False) != getattr(oa, "weak_type",
                                                        False):
            findings.append(_finding(
                "ZC105",
                f"{label} carry leaf {leaf} weak-type drift "
                f"({ia.weak_type} -> {oa.weak_type})",
                "a weakly-typed carry leaf (a bare Python scalar in the "
                "initial state) strengthens after one window — one "
                "silent retrace; seed the state with committed dtypes"))
    return findings


# -------------------------------------------------------------- certify --
def certify_engine(engine: Callable, state, shell, stack, *,
                   scope=None, state_is_factory: bool = False,
                   name: str = "engine") -> CertReport:
    """Certify one engine against the rule catalog. ``state``/``shell``/
    ``stack`` are the initial trees (concrete or already-abstract — they
    are abstractified before any tracing). ``state_is_factory`` says the
    job rebuilds its initial state per attempt (``FarmJob.state`` is
    callable), which is what makes donation replay-safe (ZC103).
    ``scope`` is the job's ScopeSpec (or None) for the fused-plane rule
    (ZC107)."""
    report = CertReport(name=name)
    if engine is None:
        report.findings.append(_finding(
            "ZC100", "job has no engine", "nothing to certify"))
        return report
    avals = _abstractify((state, shell, stack))
    try:
        closed, out_struct = jax.make_jaxpr(
            engine, return_shape=True)(*avals)
    except Exception as e:      # noqa: BLE001 — uncertifiable, not fatal
        report.findings.append(_finding(
            "ZC100", "engine failed abstract tracing",
            f"{type(e).__name__}: {e}"))
        return report

    # ---- jaxpr-walking rules
    report.findings.extend(_find_callbacks(closed.jaxpr))

    # ---- donation rules
    donated = _donated_argnums(engine, avals)
    if any(i != 0 for i in donated):
        bad = sorted(i for i in donated if i != 0)
        names = {1: "shell", 2: "batch_stack"}
        report.findings.append(_finding(
            "ZC102",
            "engine donates non-state argnum(s) "
            + ", ".join(f"{i} ({names.get(i, '?')})" for i in bad),
            "only the model/opt state (arg 0) may be donated: the shell "
            "snapshot and the window stack must survive the dispatch "
            "for drain and replay"))
    if 0 in donated and not state_is_factory:
        report.findings.append(_finding(
            "ZC103",
            "donating engine with a non-factory initial state",
            "requeue replays from FarmJob.state; after the first "
            "dispatch donates it, replay reads a deleted buffer (the "
            "PR 5 'Array has been deleted' class) — make FarmJob.state "
            "a zero-arg factory"))
    if donated and scope is not None and getattr(scope, "fuse", False):
        report.findings.append(_finding(
            "ZC107",
            "ScopeSpec(fuse=True) plane over a donating engine",
            "the fused counter update is traced into the same dispatch "
            "and reads DUT leaves the engine donates — run the plane "
            "unfused (fuse=False) or stop donating"))

    # ---- carry contract rules
    state_avals, state_def = tu.tree_flatten(avals[0])
    shell_avals, shell_def = tu.tree_flatten(avals[1])
    if not (isinstance(out_struct, tuple) and len(out_struct) == 3):
        report.findings.append(_finding(
            "ZC104",
            "engine does not return a (state, shell, ys) triple",
            f"returned structure: {tu.tree_structure(out_struct)}"))
        return report
    out_avals = list(closed.out_avals)
    n_out_state = tu.tree_structure(out_struct[0]).num_leaves
    n_out_shell = tu.tree_structure(out_struct[1]).num_leaves
    report.findings.extend(_compare_carry(
        "state", state_avals, state_def, out_struct[0],
        out_avals[:n_out_state]))
    report.findings.extend(_compare_carry(
        "shell", shell_avals, shell_def, out_struct[1],
        out_avals[n_out_state:n_out_state + n_out_shell]))

    # ---- PRNG discipline
    in_def = tu.tree_structure(avals)
    report.findings.extend(
        _find_key_reuse(closed, in_def, len(state_avals)))
    return report


def certify_job(job) -> CertReport:
    """Certify a built :class:`~repro.farm.manager.FarmJob` (duck-typed:
    anything with engine/windows/state/shell/stack_fn/scope). The first
    window is stacked host-side to shape the batch-stack argument; the
    engine itself is only ever traced abstractly."""
    name = getattr(job, "name", "job")
    engine = getattr(job, "engine", None)
    if engine is None:
        r = CertReport(name=name)
        r.findings.append(_finding(
            "ZC100", "job has no engine", "nothing to certify"))
        return r
    try:
        win0 = next(job._window_iter(), None)
    except Exception:   # noqa: BLE001 — duck-typed job without the helper
        windows = getattr(job, "windows", None)
        w = windows() if callable(windows) else windows
        win0 = next(iter(w), None) if w is not None else None
    if win0 is None:
        return CertReport(name=name)    # no windows: nothing dispatches
    stack_fn = getattr(job, "stack_fn", None)
    stack = stack_fn(win0) if stack_fn is not None else win0
    state = getattr(job, "state", None)
    shell = getattr(job, "shell", None)
    return certify_engine(
        engine,
        state() if callable(state) else state,
        shell() if callable(shell) else shell,
        stack,
        scope=getattr(job, "scope", None),
        state_is_factory=callable(state),
        name=name)


def certify_spec(spec, registry=None) -> CertReport:
    """Build a :class:`~repro.farm.registry.JobSpec` and certify the
    resulting job (the ``python -m repro.analysis`` path; the factory
    itself may touch devices to build its initial trees — certification
    of the ENGINE stays trace-only)."""
    return certify_job(spec.build(registry))


# ----------------------------------------------------- no-device guard --
@contextlib.contextmanager
def no_dispatch_guard():
    """Fail-fast context proving certification never reaches a device:
    any backend compile inside the block raises. Abstract eval and
    StableHLO lowering never compile, so every boardcheck pass must run
    clean under this guard (tests hold certification to it)."""
    from jax._src import compiler as _compiler
    real = _compiler.backend_compile_and_load

    def _blocked(*args, **kwargs):
        raise AssertionError(
            "device compile during certification — boardcheck must be "
            "trace-only (abstract eval, no dispatch)")

    _compiler.backend_compile_and_load = _blocked
    try:
        yield
    finally:
        _compiler.backend_compile_and_load = real
